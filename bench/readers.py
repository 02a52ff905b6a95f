"""Helpers the per-layer metric readers share: spans of the window."""
from __future__ import annotations

from typing import Dict, List


def window_spans(ctx: Dict) -> List[tuple]:
    """The span tracer's spans (name, start, end in microseconds) that lie
    inside the window's service steps."""
    spans = ctx["spans"]
    steps = [s for s in spans if s[0] == "service.step"][:len(ctx["window"].steps)]
    if not steps:
        return []
    lo, hi = steps[0][1], steps[-1][2]
    return [s for s in spans if s[1] >= lo and s[2] <= hi]


def per_step_ms(ctx: Dict, name: str):
    """Summed durations of ``name`` spans per window step, in ms."""
    spans = window_spans(ctx)
    n = sum(1 for s in spans if s[0] == "service.step")
    if not n:
        return None
    return sum(e - s for nm, s, e in spans if nm == name) / n / 1e3
