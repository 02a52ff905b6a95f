"""Operations and bytes the work requires, counted from shapes.

The same work is counted whatever implements it, so a later change that
pads less, fuses differently or takes a kernel off the path moves the
measured time and not the count.

Conventions (per layer unless said otherwise):

* a matrix product of ``m`` rows with a ``[k, n]`` weight is ``2 m k n``;
* training counts the forward pass and the activation gradients of the
  backward pass (the backbone is frozen: no weight gradients), so a frozen
  matmul costs ``4`` FLOPs per weight per token; recomputation under
  ``remat`` and padding tokens are not counted;
* causal attention counts the (query, key) pairs inside each sequence plus
  each query's prefix rows; a pair costs ``4 dh`` per query head forward
  (scores and the weighted sum) and twice that backward;
* an adapter counts its own tenant's rank: forward, activation gradient and
  its own weight gradient (3x the forward).

Each block family counts its own matmuls and attention by these
conventions (``bench/blocks/<family>.py``); the counts here dispatch on the
configuration's family.
"""
from __future__ import annotations

from typing import Dict, Iterable

import blocks


def site_dims(cfg: Dict) -> Dict[str, tuple]:
    """(d_in, d_out) of every matmul an adapter can attach to."""
    return blocks.family(cfg).site_dims(cfg)


def matmul_params(cfg: Dict) -> int:
    """Weights every token is multiplied by, over all layers and the LM
    head."""
    return blocks.family(cfg).matmul_params(cfg)


def attention_pairs(lengths: Iterable[int], prefix_rows: int = 0) -> int:
    """Causal (query, key) pairs of a set of sequences, plus every query's
    prefix rows."""
    return sum(l * (l + 1) // 2 + l * prefix_rows for l in lengths)


def train_flops(cfg: Dict, tenants) -> float:
    """Required FLOPs of one training iteration: every tenant's frozen
    lengths (one micro-batch each), forward plus backward."""
    return blocks.family(cfg).train_flops(cfg, tenants)
