"""Device trace: milliseconds per optimizer step in the fused stem's Mosaic
calls on chip 0 (forward with the window index, and backward). The
``pallas_call``s have no name yet (PERF.md section 7, for the tracing issue)
and their HLO names follow whatever scope wraps them (``bn1.20`` on one chip,
``shard_map.381`` on four), so they are told by what they produce: a
``tpu_custom_call`` whose first result has the shape of the stem's input
(backward: dy) or of its pooled output (forward), in the kernel's transposed
layout [H, W, 64, batch on this chip]."""

from benchmark.trace import reduce


def read(obs, trace):
    if trace is None or 0 not in trace.devices:
        return None
    h = obs["model"]["image_size"] // 2
    batch = obs["global_batch"] // obs["chips"]
    shapes = {f"bf16[{h},{h},64,{batch}]", f"bf16[{h // 2},{h // 2},64,{batch}]"}

    def is_stem(label: str) -> bool:
        return bool(reduce.MOSAIC.match(label)) and label.split()[2] in shapes

    got = reduce.per_step(trace, 0, obs["steps_per_program"], pick=is_stem)
    return got[0] / 1e6 if got and got[0] > 0 else None
