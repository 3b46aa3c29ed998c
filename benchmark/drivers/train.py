"""Job kind ``train``: one run of ``mpi_pytorch_tpu.train.trainer.main``.

The cell is what a user runs — ``python -m mpi_pytorch_tpu.train`` with the
configuration's and the traffic file's flags — entered through the same
``main(argv)`` in this process (one process per chip). Nothing of the
trainer is rebuilt here. The harness stays outside it:

- a watcher thread tails the trainer's metrics file and stamps, on the
  harness's own clock, the moment each ``kind="epoch"`` record appears;
- after ``warmup_epochs`` epochs the measured window opens (set-up ends);
- with ``--trace 1`` the same thread starts the JAX profiler at an epoch
  boundary and stops it ``trace_epochs`` + 1 boundaries later, so the trace
  holds that many whole epochs and never the compile;
- ``--seconds`` after the window opened a timer touches ``--preempt-file``;
  the trainer stops at its next safe boundary (epoch boundary when the epoch
  is one scanned program, step boundary when it streams) and returns
  normally. A timer of its own, because writing a trace out can hold the
  watcher for seconds.

Validation and checkpoints are off inside the window (traffic flags).
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time

NUM_EPOCHS = 1_000_000  # never reached: the preempt file ends the run
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def argv(flags: dict) -> list[str]:
    """``{"device-cache": True}`` -> ``["--device-cache", "true"]``."""
    out = []
    for key, value in flags.items():
        if isinstance(value, bool):
            value = "true" if value else "false"
        out += [f"--{key}", str(value)]
    return out


class Watcher(threading.Thread):
    """Tails the metrics file; owns the window, the profiler and the stop."""

    def __init__(self, *, metrics_file, preempt_file, profile_dir, seconds,
                 warmup_epochs, trace, trace_epochs):
        super().__init__(name="bench-watcher", daemon=True)
        self.metrics_file = metrics_file
        self.preempt_file = preempt_file
        self.profile_dir = profile_dir
        self.seconds = seconds
        self.warmup_epochs = warmup_epochs
        self.trace = trace
        self.trace_epochs = trace_epochs
        self.epoch_marks: list[tuple[float, dict]] = []  # (harness clock, record)
        self.window_start: float | None = None
        self.error: BaseException | None = None
        self._done = threading.Event()
        self._stop_timer = threading.Timer(seconds, self._touch_stop)

    def _touch_stop(self) -> None:
        open(self.preempt_file, "w").close()

    def finish(self) -> None:
        self._done.set()
        self._stop_timer.cancel()
        self.join(timeout=300)

    def run(self) -> None:
        try:
            self._run()
        except BaseException as e:  # surfaced by the driver after main returns
            self.error = e
            self._touch_stop()  # never leave the trainer running for a million epochs

    def _run(self) -> None:
        import jax

        offset = 0
        tracing_from = None  # epochs seen when the trace began
        while not self._done.is_set():
            time.sleep(0.001)
            now = time.perf_counter()
            try:
                size = os.path.getsize(self.metrics_file)
            except OSError:
                continue
            if size == offset:
                continue
            with open(self.metrics_file, "rb") as f:
                f.seek(offset)
                chunk = f.read()
            # Only whole lines: the writer is line-buffered, but a read can
            # land between a line's bytes and its newline.
            whole = chunk.rfind(b"\n") + 1
            offset += whole
            for line in chunk[:whole].splitlines():
                rec = json.loads(line)
                if rec.get("kind") != "epoch":
                    continue
                self.epoch_marks.append((now, rec))
                n = len(self.epoch_marks)
                if n == self.warmup_epochs:
                    self.window_start = now
                    self._stop_timer.start()
                    if self.trace:
                        options = jax.profiler.ProfileOptions()
                        # Annotations (level 1) and no more: at level 2 the
                        # runtime's transfer threads wrote 285 MB in 16 s of
                        # the streaming cell (PR 22).
                        options.python_tracer_level = 0
                        options.host_tracer_level = 1
                        jax.profiler.start_trace(self.profile_dir, profiler_options=options)
                        tracing_from = n
                elif tracing_from is not None and n - tracing_from > self.trace_epochs:
                    jax.profiler.stop_trace()
                    tracing_from = None
        if tracing_from is not None:  # the run ended inside the trace
            jax.profiler.stop_trace()


def run(ctx: dict) -> dict:
    """Run the cell once; returns the observations the metric readers and
    the correctness check work from."""
    import jax

    from benchmark import tasks
    # First, so that a directory without the system fails before any work.
    from mpi_pytorch_tpu.train import trainer

    config, traffic = ctx["config"], ctx["traffic"]
    out = ctx["out_dir"]
    chips = ctx["chips"]
    task = tasks.load(config)  # what a sample is: the dataset step below is its
    model = config["model"]
    batch = config["batch_per_chip"] * chips
    recipe = dict(traffic["dataset"])

    flags = dict(config["flags"])
    flags.update(traffic["flags"])
    if ctx["trace"]:
        flags.update(traffic.get("trace_flags", {}))
    if ctx["rehearse"]:
        # CPU, tiny, kernels interpreted: control flow only, never a result.
        model = dict(model, **config["rehearse"]["model"])
        flags.update(config["rehearse"]["flags"])
        os.environ.update(config["rehearse"]["env"])
        recipe.update(traffic["rehearse"]["dataset"])
        batch = traffic["rehearse"]["batch_per_chip"] * chips
    flags.update(task.ensure(recipe, model, seed=ctx["seed"], data_root=ctx["data_root"]))
    flags.update(task.model_flags(model))
    flags.update(
        {
            "seed": ctx["seed"],
            "batch-size": batch,
            "num-epochs": NUM_EPOCHS,
            "preempt-file": os.path.join(out, "stop"),
            "metrics-file": os.path.join(out, "metrics.jsonl"),
            "trace-file": os.path.join(out, "spans.json"),
            "log-file": os.path.join(out, "training.log"),
            "checkpoint-dir": os.path.join(out, "checkpoints"),
        }
    )

    compiles: list[float] = []  # harness-clock time of every backend compile

    def on_duration(name: str, _secs: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    watcher = Watcher(
        metrics_file=flags["metrics-file"], preempt_file=flags["preempt-file"],
        profile_dir=os.path.join(out, "profile"), seconds=ctx["seconds"],
        warmup_epochs=traffic["warmup_epochs"], trace=ctx["trace"],
        trace_epochs=traffic["trace_epochs"],
    )
    watcher.start()
    try:
        trainer.main(argv(flags))
    finally:
        watcher.finish()
    t_end = time.perf_counter()
    if watcher.error is not None:
        raise watcher.error
    if watcher.window_start is None:
        raise RuntimeError("the trainer returned before the warm-up epochs were over")

    stats = [d.memory_stats() for d in jax.local_devices()]
    # The allocator's peak does not hold the running program's temporaries on
    # this runtime: it RESERVES them apart (``peak_bytes_reserved`` equals the
    # compiler's temp size, PERF.md section 6, PR 22). The chip held both.
    peak = (
        max(int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved", 0)) for s in stats)
        if all(stats) else None
    )
    print(f"benchmark: memory_stats {stats[0]}", flush=True)
    with open(flags["metrics-file"]) as f:
        records = [json.loads(line) for line in f if line.strip()]
    with open(flags["trace-file"]) as f:
        spans = json.load(f)["traceEvents"]
    xplanes = glob.glob(os.path.join(out, "profile", "plugins", "profile", "*", "*.xplane.pb"))
    n_train = task.train_samples(recipe)
    marks = [t for t, _ in watcher.epoch_marks[traffic["warmup_epochs"] - 1:]]
    print("benchmark: epoch intervals in the window (s):",
          [round(b - a, 4) for a, b in zip(marks, marks[1:])], flush=True)
    return {
        "flags": flags,
        "model": model,
        "reference": config["reference"],
        "global_batch": batch,
        "steps_per_epoch": n_train // batch,  # drop_remainder, the trainer's default
        # Optimizer steps one execution of the step program holds.
        "steps_per_program": n_train // batch if flags.get("scan-epoch") else 1,
        "chips": chips,
        "warmup_epochs": traffic["warmup_epochs"],
        "t_start": ctx["t_start"],
        "window_start": watcher.window_start,
        "t_end": t_end,
        "epoch_marks": watcher.epoch_marks,
        "compiles": compiles,
        "records": records,
        "spans": spans,
        "xplane": xplanes[0] if xplanes else None,
        "peak_bytes": peak,
    }
