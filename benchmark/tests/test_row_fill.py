"""``moe.row_fill_pct`` on hand-made epoch records: with the counter, and
without it (what the parent commit writes: the metric is left out)."""

import json
import os

import pytest

from benchmark.metrics import load_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _obs(records):
    return {"epoch_marks": [(float(i), rec) for i, rec in enumerate(records)], "warmup_epochs": 1}


def test_row_fill_is_the_held_pairs_over_the_rows_computed():
    rec = lambda held, rows: {"kind": "epoch", "moe_pairs_held": held, "moe_pairs_absent": 0,
                              "moe_load_max": 1, "moe_rows_computed": rows}
    # The warm-up epoch does not count; 300 000 pairs in 524 288 rows after it.
    obs = _obs([rec(1, 1), rec(100_000, 262_144), rec(200_000, 262_144)])
    assert load_reader("moe.row_fill_pct")(obs, None) == pytest.approx(100 * 300_000 / 524_288)


@pytest.mark.parametrize("records", [
    [{"kind": "epoch"}] * 3,  # a model without expert layers
    [{"kind": "epoch", "moe_pairs_held": 9, "moe_pairs_absent": 0, "moe_load_max": 1}] * 3,  # the parent
])
def test_records_without_the_counter_read_as_nothing(records):
    assert load_reader("moe.row_fill_pct")(_obs(records), None) is None


def test_the_metric_is_declared_for_the_token_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == "moe.row_fill_pct")
    assert entry == {
        "name": "moe.row_fill_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "expert layer", "moves": "img_per_s_chip", "workloads": ["lfm2_train_hbm_8k"],
    }
