# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: each module maps to one paper table/figure.

  Fig 14/15 -> throughput     Fig 16 -> breakdown    Fig 17 -> memory
  Fig 18/19 -> orchestration  Fig 20 -> alignment    Fig 21 -> scalability
  Eq 3-6    -> planner_quality            kernels -> grouped-kernel claim
  §Roofline -> roofline (reads artifacts/dryrun)   serve_trace -> §5.4 online

A module that raises prints an ``<module>/ERROR`` row and the run exits 1
after the remaining modules.

``--json`` additionally writes one ``BENCH_<module>.json`` artifact per
module run ({row name -> us_per_call}) so the perf trajectory is tracked
across PRs by diffing artifacts instead of scraping stdout.

``--compare <dir>`` diffs the BENCH_*.json artifacts in the current
directory against baselines of the same name under <dir> (e.g. artifacts
downloaded from the previous main run), printing per-metric deltas.  Exit
code is 1 when a metric regressed beyond ``--threshold`` (default +25%,
metrics are lower-is-better) in a BLOCKING module: ``--blocking
kernels,throughput`` restricts the gate to those modules — other modules'
regressions print ``REGRESSED(advisory)`` and never fail the build.  With
no ``--blocking``, every module gates (the pre-CI local behavior).  CI
wires the kernel microbenches as the blocking slice and keeps serve /
co-serve rows advisory.

``--baseline-tag <name>`` overrides the comparison baseline: metrics are
read from the newest PINNED history run recorded with ``--tag <name>``
instead of the top-level artifacts — so a deliberate perf shift can be
judged against a blessed baseline rather than whatever ran last.

Every ``--compare`` run also APPENDS the current artifacts to
``<dir>/history/run-<n>[-<tag>]/`` and regenerates ``<dir>/DASHBOARD.md``
— a markdown table of each metric's trajectory across the retained runs,
with a unicode sparkline per metric (CI posts this file as a sticky PR
comment).  Retention policy: the newest ``--retain`` (default 8) untagged
runs are kept; runs recorded with ``--tag <name>`` are pinned baselines
and never pruned.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import time
import traceback

from repro.launch.compile_cache import configure_compile_cache
from repro.obs.log import get_logger

log = get_logger("bench")

MODULES = [
    "alignment",
    "planner_quality",
    "memory",
    "orchestration",
    "scalability",
    "kernels",
    "breakdown",
    "throughput",
    "roofline",
    "serve_trace",
    "coserve",
    "fleet",
]


# ---------------------------------------------------------------------------
# Artifact history: retention policy + markdown dashboard
# ---------------------------------------------------------------------------

_RUN_RE = re.compile(r"^run-(\d+)(?:-(.+))?$")


def _history_runs(baseline_dir: str):
    """Sorted [(seq, tag_or_None, path)] of recorded history runs."""
    out = []
    hist = os.path.join(baseline_dir, "history")
    for name in (os.listdir(hist) if os.path.isdir(hist) else []):
        m = _RUN_RE.match(name)
        if m and os.path.isdir(os.path.join(hist, name)):
            out.append((int(m.group(1)), m.group(2), os.path.join(hist, name)))
    return sorted(out)


def record_history(baseline_dir: str, retain: int = 8,
                   tag: str | None = None) -> str:
    """Append the cwd's BENCH_*.json as the next history run and prune
    untagged runs beyond ``retain`` (tagged runs are pinned baselines)."""
    runs = _history_runs(baseline_dir)
    seq = (runs[-1][0] + 1) if runs else 1
    name = f"run-{seq}" + (f"-{tag}" if tag else "")
    dst = os.path.join(baseline_dir, "history", name)
    os.makedirs(dst, exist_ok=True)
    for path in sorted(glob.glob("BENCH_*.json")):
        shutil.copy(path, os.path.join(dst, os.path.basename(path)))
    runs = _history_runs(baseline_dir)
    untagged = [r for r in runs if r[1] is None]
    for _seq, _tag, path in untagged[:max(len(untagged) - retain, 0)]:
        shutil.rmtree(path, ignore_errors=True)
    return dst


_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(vals) -> str:
    """Unicode trajectory of a metric series (None -> gap).  Scaled per
    metric min..max so the shape, not the magnitude, reads at a glance."""
    xs = [v for v in vals if v is not None]
    if not xs:
        return ""
    lo, hi = min(xs), max(xs)
    out = []
    for v in vals:
        if v is None:
            out.append(" ")
        elif hi == lo:
            out.append(_SPARK[0])
        else:
            out.append(_SPARK[round((v - lo) / (hi - lo) * (len(_SPARK) - 1))])
    return "".join(out)


def write_dashboard(baseline_dir: str, max_cols: int = 10) -> str:
    """Regenerate <dir>/DASHBOARD.md: per-module metric history across the
    retained runs (oldest -> newest; tagged runs marked with their tag),
    one unicode sparkline per metric."""
    runs = _history_runs(baseline_dir)[-max_cols:]
    lines = ["# Benchmark history", "",
             "Per-PR metric trajectory (us/call, lower is better) over the "
             f"retained runs under `history/`.  Columns are runs oldest to "
             f"newest; tagged runs are pinned baselines.", "",
             "Exception: `coserve/slo_attainment_pct` is a percentage "
             "(HIGHER is better) and advisory — co-serve rows sit outside "
             "the blocking compare gate, so a dip flags for review without "
             "failing the build.", ""]
    modules: dict[str, dict[str, dict[int, float]]] = {}
    for seq, _tag, path in runs:
        for art in sorted(glob.glob(os.path.join(path, "BENCH_*.json"))):
            mod = os.path.basename(art)[len("BENCH_"):-len(".json")]
            with open(art) as f:
                data = json.load(f)
            tbl = modules.setdefault(mod, {})
            for metric, val in data.items():
                tbl.setdefault(metric, {})[seq] = float(val)
    cols = [(seq, tag) for seq, tag, _ in runs]
    for mod in sorted(modules):
        lines.append(f"## {mod}")
        lines.append("")
        head = " | ".join(f"run-{s}" + (f" ({t})" if t else "")
                          for s, t in cols)
        lines.append(f"| metric | trend | {head} |")
        lines.append("|" + "---|" * (len(cols) + 2))
        for metric in sorted(modules[mod]):
            vals = modules[mod][metric]
            series = [vals.get(s) for s, _t in cols]
            cells = []
            prev = None
            for v in series:
                if v is None:
                    cells.append("")
                elif prev not in (None, 0.0) and abs(v / prev - 1) > 0.25:
                    cells.append(f"**{v:.1f}**")  # >25% move vs prior run
                else:
                    cells.append(f"{v:.1f}")
                prev = v if v is not None else prev
            lines.append(f"| {metric} | `{sparkline(series)}` | "
                         + " | ".join(cells) + " |")
        lines.append("")
    out = os.path.join(baseline_dir, "DASHBOARD.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    return out


def compare(baseline_dir: str, threshold: float, bootstrap: bool = True,
            retain: int = 8, tag: str | None = None,
            blocking: set[str] | None = None,
            baseline_tag: str | None = None) -> int:
    """Cross-PR bench diff: current ./BENCH_*.json vs baseline_dir's.

    ``blocking`` restricts the failing exit code to regressions in those
    modules (others are printed as advisory); ``None`` gates every module.
    ``baseline_tag`` reads the baseline metrics from the newest history run
    pinned with that ``--tag`` instead of the top-level artifacts.

    First-run bootstrap: when the baseline directory is missing or holds no
    artifacts (a fresh repo, expired artifact retention, or a renamed CI
    artifact), the current artifacts are seeded INTO it and the compare
    passes — so the very first CI run establishes the baseline instead of
    failing the fetch.  Every call also appends the current artifacts to the
    baseline's history (``--retain``/``--tag`` policy) and regenerates the
    DASHBOARD.md metric-trajectory table."""
    current = sorted(glob.glob("BENCH_*.json"))
    if not current:
        log.error("no BENCH_*.json in %s to compare", os.getcwd())
        return 2
    base_src = baseline_dir
    if baseline_tag is not None:
        pinned = [r for r in _history_runs(baseline_dir)
                  if r[1] == baseline_tag]
        if not pinned:
            log.error("no pinned history run tagged '%s' under %s",
                      baseline_tag, baseline_dir)
            return 2
        base_src = pinned[-1][2]
        log.info("baseline override: pinned %s", os.path.basename(base_src))
    baseline_files = sorted(glob.glob(os.path.join(base_src, "BENCH_*.json")))
    if not baseline_files:
        if not bootstrap:
            log.error("no baseline artifacts under %s", baseline_dir)
            return 2
        os.makedirs(baseline_dir, exist_ok=True)
        for path in current:
            shutil.copy(path, os.path.join(baseline_dir, os.path.basename(path)))
        record_history(baseline_dir, retain=retain, tag=tag)
        write_dashboard(baseline_dir)
        log.info("bootstrap: no baseline under %s; seeded %d artifact(s) "
                 "as the new baseline", baseline_dir, len(current))
        return 0
    regressions = 0
    advisory = 0
    compared = 0
    print("module,metric,baseline_us,current_us,delta_pct,flag")
    for path in current:
        name = os.path.basename(path)
        base_path = os.path.join(base_src, name)
        mod = name[len("BENCH_"):-len(".json")]
        gates = blocking is None or mod in blocking
        if not os.path.exists(base_path):
            print(f"{mod},<module>,,,,NEW")
            continue
        with open(path) as f:
            cur = json.load(f)
        with open(base_path) as f:
            base = json.load(f)
        for metric in sorted(set(cur) | set(base)):
            if metric not in base:
                print(f"{mod},{metric},,{cur[metric]:.1f},,NEW")
                continue
            if metric not in cur:
                print(f"{mod},{metric},{base[metric]:.1f},,,REMOVED")
                continue
            b, c = float(base[metric]), float(cur[metric])
            delta = (c - b) / b if b else 0.0
            flag = "ok"
            if delta > threshold:
                if gates:
                    flag = "REGRESSED"
                    regressions += 1
                else:
                    flag = "REGRESSED(advisory)"
                    advisory += 1
            elif delta < -threshold:
                flag = "improved"
            compared += 1
            print(f"{mod},{metric},{b:.1f},{c:.1f},{delta * 100:+.1f},{flag}")
    log.info("compared %d metrics, %d blocking + %d advisory "
             "regression(s) beyond +%.0f%%", compared, regressions,
             advisory, threshold * 100)
    dst = record_history(baseline_dir, retain=retain, tag=tag)
    dash = write_dashboard(baseline_dir)
    log.info("history: recorded %s, dashboard %s", os.path.basename(dst), dash)
    return 1 if regressions else 0


def main() -> None:
    args = sys.argv[1:]
    as_json = "--json" in args
    compare_dir = None
    threshold = 0.25
    retain = 8
    tag = None
    blocking = None
    baseline_tag = None
    only = []
    i = 0
    while i < len(args):
        a = args[i]
        if a in ("--compare", "--threshold", "--retain", "--tag",
                 "--blocking", "--baseline-tag"):
            i += 1
            if i >= len(args):
                # usage error: distinct from the rc=1 "regression" signal
                log.error("%s requires a value", a)
                sys.exit(2)
            if a == "--compare":
                compare_dir = args[i]
            elif a == "--threshold":
                threshold = float(args[i])
            elif a == "--retain":
                retain = int(args[i])
            elif a == "--blocking":
                blocking = {m.strip() for m in args[i].split(",") if m.strip()}
            elif a == "--baseline-tag":
                baseline_tag = args[i]
            else:
                tag = args[i]
        elif not a.startswith("--"):
            only.append(a)
        i += 1
    if compare_dir is not None:
        sys.exit(compare(compare_dir, threshold, retain=retain, tag=tag,
                         blocking=blocking, baseline_tag=baseline_tag))

    configure_compile_cache()
    print("name,us_per_call,derived")
    failed: list[str] = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        rows: list[str] = []
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                rows.append(row)
                print(row, flush=True)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        if as_json and rows:
            # no artifact for a module that errored before producing rows —
            # an empty BENCH_*.json would let CI's artifact check go green
            # with no benchmark data behind it.
            art = {}
            for row in rows:
                parts = row.split(",")
                if len(parts) >= 2:
                    try:
                        art[parts[0]] = float(parts[1])
                    except ValueError:
                        pass
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump(art, f, indent=2, sort_keys=True)
            log.info("wrote %s (%d rows)", path, len(art))
        log.info("%s done in %.1fs", name, time.time() - t0)
    if failed:
        log.error("modules that raised: %s", ",".join(failed))
        sys.exit(1)


if __name__ == "__main__":
    main()
