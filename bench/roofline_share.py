"""A kernel's share of its roofline, for the per-layer readers: the least
time the chip could take for the work the block family counts
(``scan_work``), over the kernel's measured device time per traced step."""
from __future__ import annotations

from typing import Dict, Optional

import blocks
from devtime import kernel_ms_per_step


def scan_roofline(ctx: Dict, phase: str, *kernels: str) -> Optional[float]:
    """``phase`` (``fwd`` or ``bwd``) of the family's scan work at one
    step's tokens (each tenant's micro-batch) against ``kernels``' time;
    None where the family counts no scan or the trace holds none of them."""
    cfg = ctx["cell"].cfg
    counts = getattr(blocks.family(cfg), "scan_work", None)
    ms = kernel_ms_per_step(ctx["trace"], *kernels)
    if counts is None or not ms:
        return None
    tokens = sum(sum(t.lengths) for t in ctx["run"].tenants)
    w = counts(cfg, tokens)
    pk = ctx["peaks"]
    least_s = max(w[f"{phase}_flops"] / pk.bf16_flops,
                  w[f"{phase}_bytes"] / pk.hbm_bytes_per_s)
    return 100.0 * least_s / (ms / 1e3)
