"""Block sizes the TPU's Pallas lowering accepts.

The last two dims of every block must be divisible by (8, 128) — sublanes
by lanes — or equal the array's own dims.  Every kernel picks its tiles
through :func:`fit_block`, in interpret mode too, so the CPU tests run the
tiling the chip compiles.
"""
from __future__ import annotations

SUBLANE = 8
LANE = 128


def fit_block(dim: int, want: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``dim`` and is at most
    ``want``; ``dim`` itself (always legal) when no such multiple exists."""
    b = min(want, dim) // align * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim
