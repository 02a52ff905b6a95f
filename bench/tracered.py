"""Reduction of a profiler trace to what the per-layer metrics read.

Two stages.  ``extract`` turns the profiler's ``.xplane.pb`` into plain
lists (device operations, device programs, host spans) with times in
nanoseconds on the trace's one clock; ``Reduced`` computes from those
lists: the union of device-busy intervals, the idle gaps and the innermost
host span open in each, the device time of each program and kernel.  The
second stage is what the tests check, on a small recorded excerpt.

On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Modules``
line holds one event per executed program (``jit_step(<id>)``, one per
hTask step; ``jit_decode_micro``; ``jit_bind_n``), and their ``XLA Ops``
line one event per executed HLO operation, named by the instruction's
text, nested: a ``while`` (a layer scan) is an event and so is every
operation inside it.  An operation's program is the module event that
holds it.  A Pallas kernel is a ``custom_call_target="tpu_custom_call"``
instruction; the trace carries no kernel name or source file for it.
Host spans (``jax.profiler.TraceAnnotation``, which the
program's enabled tracer and the benchmark open) are events on the host
plane's threads.
"""
from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: host span names the reduction attributes idle time to (the program's
#: span sites and the benchmark's own)
SPAN_PREFIXES = ("service.", "engine.", "decode.", "bench.", "tenant.")


def _stats(ev) -> Dict[str, object]:
    out = {}
    for k, v in ev.stats:
        out[str(k)] = v if isinstance(v, (int, float, str)) else str(v)
    return out


def _module_name(name: str) -> str:
    """``jit_step(5888...)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def _attribute(ops: List[Dict], modules: List[Dict]) -> None:
    """Give each operation the program it ran in (the module event that
    contains its start) and mark the operations that contain others (a
    ``while`` around a layer scan is an event of its own, and so is every
    operation inside it): only leaves are operations proper."""
    import bisect

    mods = sorted(modules, key=lambda m: m["start"])
    starts = [m["start"] for m in mods]
    for o in ops:
        i = bisect.bisect_right(starts, o["start"]) - 1
        inside = i >= 0 and o["start"] < mods[i]["start"] + mods[i]["dur"]
        o["module"] = _module_name(mods[i]["name"]) if inside else ""
        o["leaf"] = True
    stack: List[Dict] = []
    for o in sorted(ops, key=lambda o: (o["start"], -o["dur"])):
        while stack and stack[-1]["start"] + stack[-1]["dur"] <= o["start"]:
            stack.pop()
        if stack and o["start"] + o["dur"] <= stack[-1]["start"] + stack[-1]["dur"]:
            stack[-1]["leaf"] = False
        stack.append(o)


def extract(trace_dir: str) -> Dict[str, List[Dict]]:
    """Plain lists from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[-1]
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [{"dev": dev, "name": ev.name, "start": float(ev.start_ns),
                            "dur": float(ev.duration_ns)} for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [{"dev": dev, "name": _module_name(ev.name),
                             "start": float(ev.start_ns), "dur": float(ev.duration_ns)}
                            for ev in line.events]
            _attribute(ops, mods)
            out["ops"].extend(ops)
            out["modules"].extend(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        out["host"].append({
                            "tid": line.name, "name": ev.name,
                            "start": float(ev.start_ns), "dur": float(ev.duration_ns)})
    return out


def dump_summary(trace_dir: str, path: str, excerpt_ns: float = 5e7) -> None:
    """For a look by hand: every plane and line with its event count, the
    first events of each line with all their stats, and an excerpt of the
    extracted lists (the first ``excerpt_ns`` of device activity)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    pd = ProfileData.from_file(paths[-1])
    summary = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs),
                          "first": [{"name": e.name, "start": e.start_ns,
                                     "dur": e.duration_ns, "stats": _stats(e)}
                                    for e in evs[:12]]})
        summary.append({"plane": plane.name, "lines": lines})
    ex = extract(trace_dir)
    t0 = min((o["start"] for o in ex["ops"]), default=0.0)
    cut = {k: [e for e in v if t0 <= e["start"] < t0 + excerpt_ns]
           for k, v in ex.items()}
    with open(path, "w") as f:
        json.dump({"planes": summary, "excerpt": cut}, f, default=str)


# ---------------------------------------------------------------------------
# reduction


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclass
class Reduced:
    """A traced window ``[t0, t1)`` and what ran in it."""

    t0: float
    t1: float
    ops: List[Dict]
    modules: List[Dict]
    host: List[Dict]
    devices: List[str] = field(default_factory=list)

    @classmethod
    def from_lists(cls, lists: Dict[str, List[Dict]], t0: float,
                   t1: float) -> "Reduced":
        ops = [o for o in lists["ops"] if o["start"] < t1 and o["start"] + o["dur"] > t0]
        mods = [m for m in lists["modules"]
                if m["start"] < t1 and m["start"] + m["dur"] > t0]
        host = [h for h in lists["host"] if h["start"] < t1 and h["start"] + h["dur"] > t0]
        devs = sorted({o["dev"] for o in ops} | {m["dev"] for m in mods})
        return cls(t0, t1, ops, mods, host, devs)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy(self, dev: Optional[str] = None) -> List[Tuple[float, float]]:
        """Union of the intervals in which an operation ran on the device."""
        src = [(o["start"], o["start"] + o["dur"]) for o in self.ops
               if dev is None or o["dev"] == dev]
        return clip(union(src), self.t0, self.t1)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices used."""
        devs = self.devices or [None]
        tot = sum(sum(e - s for s, e in self.busy(d)) for d in devs)
        return tot / len(devs) / 1e9

    def idle_gaps(self, dev: Optional[str] = None) -> List[Tuple[float, float]]:
        busy = self.busy(dev or (self.devices[0] if self.devices else None))
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        return gaps

    def innermost_span(self, t: float) -> str:
        """The shortest host span open at time ``t``: what the host was
        doing then ("host" when no span of the program or benchmark was)."""
        best, best_d = "host", float("inf")
        for h in self.host:
            if h["start"] <= t < h["start"] + h["dur"] and h["dur"] < best_d:
                best, best_d = h["name"], h["dur"]
        return best

    def longest_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:n]
        return [(self.innermost_span(0.5 * (s + e)), (e - s) / 1e9) for s, e in gaps]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations by total time (name with its program)."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            if not o.get("leaf", True):
                continue
            k = op_label(o)
            tot[k] = tot.get(k, 0.0) + o["dur"]
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / 1e9) for k, v in top]

    def module_time(self, match) -> Tuple[float, int]:
        """Device seconds and executions of the programs whose name
        ``match`` accepts."""
        ms = [m for m in self.modules if match(m["name"])]
        return sum(m["dur"] for m in ms) / 1e9, len(ms)

    def op_time(self, match) -> float:
        """Device seconds of the operations (leaves) ``match(op)`` accepts."""
        return sum(o["dur"] for o in self.ops
                   if o.get("leaf", True) and match(o)) / 1e9


def op_label(op: Dict) -> str:
    """A short stable label of a device operation: its program, the HLO
    instruction's kind and, for a custom call, its target (a Pallas kernel
    is a ``tpu_custom_call``)."""
    import re

    name = op["name"]
    m = re.match(r"%?([A-Za-z_\-]+?)(?:[.\d]*)\s*=", name)
    kind = m.group(1) if m else name.split(" ", 1)[0]
    t = re.search(r'custom_call_target="([^"]+)"', name)
    if t:
        kind = f"custom-call:{t.group(1)}"
    shape = re.search(r"=\s*\(?([a-z0-9]+\[[0-9,]*\])", name)
    return f"{op['module'] or '-'}/{kind}" + (f" {shape.group(1)}" if shape else "")


def is_pallas(op: Dict) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op["name"]


def self_times(spans: Sequence[Tuple[str, float, float]], name: str) -> List[float]:
    """Self time of each ``name`` span: its duration minus the part of it
    that other spans nested inside it cover.  ``spans``: (name, start, end)
    of one thread."""
    out = []
    for n, s, e in spans:
        if n != name:
            continue
        kids = [(max(s2, s), min(e2, e)) for n2, s2, e2 in spans
                if (n2, s2, e2) != (n, s, e) and s2 >= s and e2 <= e]
        covered = sum(b - a for a, b in union(kids))
        out.append((e - s) - covered)
    return out


def tracer_spans(events) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the B/E pairs a ``SpanTracer`` recorded, in
    microseconds on its own clock, one list for all its tracks."""
    stacks: Dict[int, List[Tuple[str, float]]] = {}
    out = []
    for ph, name, ts, tid, _ in events:
        if ph == "B":
            stacks.setdefault(tid, []).append((name, ts))
        elif ph == "E" and stacks.get(tid):
            n, s = stacks[tid].pop()
            out.append((n, s, ts))
    return sorted(out, key=lambda x: x[1])
