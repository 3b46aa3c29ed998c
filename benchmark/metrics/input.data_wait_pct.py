"""Share of the window's wall time the step loop spent waiting for its next
batch: sum of ``data_wait_ms`` over the ``kind="step"`` records after
warm-up / the wall time those records span. Needs ``--step-metrics`` (the
traffic file's ``trace_flags``), which costs one host sync a step."""


def read(obs, trace):
    steps = [
        r for r in obs["records"]
        if r["kind"] == "step" and r["epoch"] >= obs["warmup_epochs"]
        and r.get("data_wait_ms") is not None
    ]
    if len(steps) < 2:
        return None
    wall_ms = (steps[-1]["ts"] - steps[0]["ts"]) * 1e3
    return 100.0 * sum(r["data_wait_ms"] for r in steps[1:]) / wall_ms
