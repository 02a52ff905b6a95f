"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, limits and per-layer metric readers
are found by name (``bench/harness.py``).  Set-up makes the weights from
the seed, admits every tenant, drives the first steps (their readings are
what the training check compares) and warms every shape the window will
use; then the window runs ``--seconds`` of whole service steps.  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the program's spans and a
profiler trace of a few whole steps inside the window.  After the window
the program's outputs are checked against the plain reference.

The last line of stdout is one JSON object; the numbers compared and their
limits are the last lines of stderr and the result's last key.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero and
prints no result.

Measurement options for building the benchmark (not used by its runs):
``--control 1`` puts the control (the plain reference one precision down)
in the program's place for the check, so ``correct`` judges the control's
numbers, and prints the program's beside them; ``--fault <name>`` plants a
fault in the program (``bench/faults.py``); ``--dump-trace <file>`` writes
the traced window's planes, lines and an excerpt for a look by hand;
``--more-seeds a,b,..`` runs the cell again in the same process for each
further seed and prints each one's readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

TRAIN_CHECK_STEPS = 3
TRAIN_WARM_STEPS = 2          # steps after the checked ones, before the window
TRACE_FIRST_STEP = 2          # the traced steps start at this window step
TRACE_SECONDS = 3.0           # and cover whole steps until this long


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs lowered for compilation (cache hits included) and
    compilations that missed the persistent cache."""

    def __init__(self):
        import jax.monitoring

        self.lowered = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _ev(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Tracing:
    """The traced run's instruments: the program's span tracer over the
    window, and a profiler trace of whole steps from ``TRACE_FIRST_STEP``
    on, until ``TRACE_SECONDS`` have passed."""

    def __init__(self, tmp: str):
        from repro.obs.tracing import SpanTracer, set_tracer

        self.dir = tmp
        self.tracer = SpanTracer(enabled=True)
        self.prev = set_tracer(self.tracer)
        self.on = False
        self.done = False
        self.t_start = 0.0
        self.first = self.last = -1

    def hook(self, i: int, when: int) -> None:
        import jax

        if self.done:
            return
        if when == 0 and i == TRACE_FIRST_STEP and not self.on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # every Python call would be an event
            opts.host_tracer_level = 2     # the annotations (program spans)
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on, self.first, self.t_start = True, i, time.perf_counter()
        if self.on and when == 1:
            self.last = i
            if time.perf_counter() - self.t_start >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                self.on, self.done = False, True

    def close(self) -> None:
        import jax
        from repro.obs.tracing import set_tracer

        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True
        set_tracer(self.prev)


def traced_window(lists, first: int, last: int):
    """The traced window on the trace's clock: from the start of the first
    traced step's host span to the end of the last one's."""
    import tracered

    steps = sorted((h for h in lists["host"] if h["name"] == "service.step"),
                   key=lambda h: h["start"])
    if not steps:
        raise RuntimeError("the trace holds no service.step span")
    sel = steps[:last - first + 1]     # the trace starts just before `first`
    return tracered.Reduced.from_lists(lists, sel[0]["start"],
                                       sel[-1]["start"] + sel[-1]["dur"])


def execute(root: Path, name: str, seed: int, seconds: float, trace: bool,
            control: bool = False, fault: str = "", dump_trace: str = "",
            t_start: float = T_START) -> dict:
    """Everything of a run after the look for a chip.  Returns the result
    object (also what the CPU tests drive)."""
    import gc

    import jax

    import check
    import harness as hz
    import refcheck

    cell = hz.find_cell(name, root)
    counter = CompileCounter()
    if fault:
        import faults

        restore = faults.plant(fault)
        try:
            return execute(root, name, seed, seconds, trace, control, "",
                           dump_trace, t_start)
        finally:
            restore()

    run = hz.build(cell, seed)
    hz.train_readings(run, TRAIN_CHECK_STEPS)
    for _ in range(TRAIN_WARM_STEPS):
        run.svc.step()
    jax.effects_barrier()
    lowered0 = counter.lowered
    log(f"set-up: {counter.lowered} programs lowered ({counter.misses} compiled, "
        f"the rest from the cache), peak_bytes_in_use {peak_bytes()}, "
        f"{TRAIN_CHECK_STEPS + TRAIN_WARM_STEPS} iterations warmed, check "
        f"readings {run.check_s:.3f} s")
    setup_s = time.perf_counter() - t_start - run.check_s

    tracing = None
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        tracing = Tracing(tmp)
    try:
        win = hz.run_window(run, seconds,
                            trace_hook=tracing.hook if tracing else None)
    finally:
        if tracing:
            tracing.close()
    log(f"window: {len(win.steps)} steps in {win.seconds:.3f} s, "
        f"{counter.lowered - lowered0} programs lowered in the window")
    peak = peak_bytes()

    per_layer = {}
    breakdown = None
    device_busy = None
    if trace:
        per_layer, breakdown, device_busy = read_per_layer(
            root, cell, run, win, tracing, tmp, dump_trace)

    hz.free_program(run)
    gc.collect()
    t_ref = time.perf_counter()
    found = refcheck.train_check(run, TRAIN_CHECK_STEPS, control=control)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    judged = "control" if control else "program"
    numbers = found[judged]
    limits = cell.limits
    correct = check.judge(numbers, limits)

    result = {"correct": bool(correct), "attempted": len(win.steps),
              "failed": sum(1 for m in win.steps if m is None)}
    if trace:
        metrics = {m["name"]: {"value": per_layer[m["name"]], "unit": m["unit"]}
                   for m in cell.per_layer if per_layer.get(m["name"]) is not None}
    else:
        train_tokens = sum(m.effective_tokens for m in win.steps if m is not None)
        values = {"train_tokens_per_s": train_tokens / win.seconds,
                  "peak_hbm_gib": peak / 2**30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if device_busy is not None:
        device["busy_s"], device["window_s"] = device_busy
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        # both sides' readings, for setting the limits
        result["readings"] = found
        for k in sorted(found["program"]):
            log(f"reading program {k}: {found['program'][k]!r}")
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in sorted(limits)}
    return result


def read_per_layer(root, cell, run, win, tracing, tmp, dump_trace):
    """Per-layer metrics of the traced run, by the cell's metric readers."""
    import shutil

    import harness as hz
    import peaks
    import tracered

    try:
        lists = tracered.extract(tmp)
        if dump_trace:
            tracered.dump_summary(tmp, dump_trace)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    red = traced_window(lists, tracing.first, tracing.last)
    import jax

    ctx = {
        "cell": cell, "run": run, "window": win, "trace": red,
        "traced_steps": list(range(tracing.first, tracing.last + 1)),
        "spans": tracered.tracer_spans(tracing.tracer.events),
        "peaks": peaks.peaks_for(jax.devices()[0].device_kind),
    }
    values = {}
    for m in cell.per_layer:
        values[m["name"]] = hz.load_metric(m["name"], root).read(ctx)
    breakdown = {"device_ops": [[k, v] for k, v in red.top_ops(10)],
                 "idle_gaps": [[k, v] for k, v in red.longest_gaps(10)]}
    return values, breakdown, (red.busy_s(), red.window_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--more-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--dump-trace", default="")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"platform: {dev.platform}")
    log(f"device_kind: {dev.device_kind}")
    log(f"device_count: {len(devices)}")
    import harness as hz

    cell = hz.find_cell(args.workload, ROOT)
    if dev.platform != "tpu" or len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} TPU chip(s); found "
            f"{len(devices)} {dev.platform} device(s): refusing to run")
        return 3

    from repro.launch.compile_cache import configure_compile_cache

    # every program in the persistent cache, and the cache large enough to
    # hold all of a cell's: a warm run then compiles nothing and writes
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"compile_cache: {configure_compile_cache()}")
    result = execute(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace), bool(args.control), args.fault,
                     args.dump_trace)
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result))
    for seed in [int(x) for x in args.more_seeds.split(",") if x]:
        # further seeds in this process, for the readings that set limits
        more = execute(ROOT, args.workload, seed, args.seconds, False,
                       bool(args.control), args.fault, "",
                       t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "correct": more["correct"],
                          "readings": more.get("readings"),
                          "checks": more["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
