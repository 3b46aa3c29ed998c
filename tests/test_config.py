import pytest

from mpi_pytorch_tpu.config import Config, parse_config


def test_defaults_mirror_reference_utils():
    # reference utils.py:4-45
    cfg = Config()
    assert cfg.model_name == "resnet18"
    assert cfg.num_classes == 64500
    assert cfg.batch_size == 128
    assert cfg.learning_rate == 4e-4
    assert cfg.num_epochs == 10
    assert cfg.width == cfg.height == 128
    assert cfg.debug is True
    assert cfg.validate is True
    assert cfg.from_checkpoint is False
    assert cfg.feature_extract is False


def test_cli_overrides():
    cfg = parse_config(["--model-name", "resnet34", "--batch-size", "32", "--debug", "false"])
    assert cfg.model_name == "resnet34"
    assert cfg.batch_size == 32
    assert cfg.debug is False


def test_invalid_model_raises():
    # reference models.py:97-99 calls exit(); we raise instead
    with pytest.raises(ValueError, match="unsupported model"):
        parse_config(["--model-name", "resnet50"])


def test_env_override(monkeypatch):
    monkeypatch.setenv("MPT_BATCH_SIZE", "16")
    assert parse_config([]).batch_size == 16


def test_inception_image_size():
    cfg = parse_config(["--model-name", "inception_v3"])
    assert cfg.image_size == (299, 299)
    assert parse_config([]).image_size == (128, 128)


def test_mesh_override():
    cfg = parse_config(["--mesh.model-parallel", "4"])
    assert cfg.mesh.model_parallel == 4


def test_debug_nans_flag_wires_jax_config():
    import jax

    from mpi_pytorch_tpu.config import apply_runtime_flags

    assert parse_config([]).debug_nans is False
    cfg = parse_config(["--debug-nans", "true"])
    assert cfg.debug_nans is True
    try:
        apply_runtime_flags(cfg)
        assert jax.config.jax_debug_nans is True
    finally:
        jax.config.update("jax_debug_nans", False)


def test_unknown_flag_errors_instead_of_silently_dropping():
    """A typo'd flag must NOT train with defaults: argparse exits with an
    'unrecognized arguments' error (strict parse_args, not parse_known_args)."""
    with pytest.raises(SystemExit):
        parse_config(["--batchsize", "64"])


def test_image_size_alias_sets_both_dims():
    cfg = parse_config(["--image-size", "64"])
    assert (cfg.width, cfg.height) == (64, 64)
    # explicit --width/--height still win over the alias
    cfg = parse_config(["--image-size", "64", "--width", "96"])
    assert (cfg.width, cfg.height) == (96, 64)


def test_image_size_env_alias(monkeypatch):
    monkeypatch.setenv("MPT_IMAGE_SIZE", "64")
    cfg = parse_config([])
    assert (cfg.width, cfg.height) == (64, 64)


def test_inception_rejects_explicit_image_size():
    with pytest.raises(ValueError, match="299"):
        parse_config(["--model-name", "inception_v3", "--image-size", "64"])
    # untouched default and explicit 299 both fine
    assert parse_config(["--model-name", "inception_v3"]).image_size == (299, 299)
    assert parse_config(
        ["--model-name", "inception_v3", "--image-size", "299"]
    ).image_size == (299, 299)


def test_env_image_size_respects_per_dim_env(monkeypatch):
    monkeypatch.setenv("MPT_IMAGE_SIZE", "64")
    monkeypatch.setenv("MPT_WIDTH", "96")
    cfg = parse_config([])
    assert (cfg.width, cfg.height) == (96, 64)


def test_inception_rejects_explicit_128_too():
    with pytest.raises(ValueError, match="299"):
        parse_config(["--model-name", "inception_v3", "--image-size", "128"])


def test_pp_stages_validation():
    """--pp-stages gates: pipeline-shaped models only, auto mode only, no
    SP/EP/accum nesting, batch divisibility — and pp_stages drives the
    mesh's pipe axis."""
    ok = parse_config(["--model-name", "vit_s16", "--pp-stages", "4"])
    assert ok.pp_stages == 4 and ok.mesh.pipe_parallel == 4

    with pytest.raises(ValueError, match="pp_stages=4 does not apply to model"):
        parse_config(["--pp-stages", "4"])  # default resnet18
    with pytest.raises(ValueError, match="pp_stages=4 does not apply to model"):
        parse_config(["--model-name", "vit_moe_s16", "--pp-stages", "4"])
    with pytest.raises(ValueError, match="auto-partitioned"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--spmd-mode", "true"])
    with pytest.raises(ValueError, match="sp-strategy|SP attention"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--sp-strategy", "ring"])
    # No model both pipelines and has experts: one of the two is refused.
    with pytest.raises(ValueError, match="ep_mesh=True does not apply to model 'vit_s16'"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--expert-parallel", "true"])
    with pytest.raises(ValueError, match="microbatches"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--accum-steps", "2"])
    with pytest.raises(ValueError, match="not divisible"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--batch-size", "130"])
    with pytest.raises(ValueError, match="fsdp"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--fsdp", "true"])
    with pytest.raises(ValueError, match="zero"):
        parse_config(["--model-name", "vit_s16", "--pp-stages", "4",
                      "--zero-optimizer", "true"])
    with pytest.raises(ValueError, match="pp_microbatches only applies"):
        parse_config(["--model-name", "vit_s16", "--pp-microbatches", "8"])


def test_parsed_compiler_options_coercion():
    """XLA's option setter needs real types (a "true" string is rejected at
    compile time — observed live), so the parser must coerce."""
    from mpi_pytorch_tpu.config import parse_config

    cfg = parse_config([
        "--compiler-options",
        "xla_tpu_scoped_vmem_limit_kib=65536 "
        "--xla_tpu_enable_latency_hiding_scheduler=true flag_c=false "
        "bare_flag name=text",
    ])
    assert cfg.parsed_compiler_options() == {
        "xla_tpu_scoped_vmem_limit_kib": 65536,
        "xla_tpu_enable_latency_hiding_scheduler": True,
        "flag_c": False,
        "bare_flag": True,
        "name": "text",
    }
    assert parse_config([]).parsed_compiler_options() is None


def test_env_flag_falsy_spellings(monkeypatch):
    """ONE definition of env truthiness (utils/env.py): any case of
    ''/'0'/'false'/'no'/'off' disables — advisor r5 found 'False'/'no'
    silently enabling MPT_FUSED_STEM in the bench harnesses."""
    from mpi_pytorch_tpu.utils.env import env_flag

    for val in ("", "0", "false", "False", "FALSE", "no", "No", "off", "OFF"):
        monkeypatch.setenv("MPT_TEST_FLAG", val)
        assert env_flag("MPT_TEST_FLAG", default=True) is False, repr(val)
    for val in ("1", "true", "True", "yes", "on"):
        monkeypatch.setenv("MPT_TEST_FLAG", val)
        assert env_flag("MPT_TEST_FLAG", default=False) is True, repr(val)
    monkeypatch.delenv("MPT_TEST_FLAG")
    assert env_flag("MPT_TEST_FLAG", default=True) is True
    assert env_flag("MPT_TEST_FLAG", default=False) is False
