"""The comparison that decides ``correct``: the program's first three
training steps against the reference's, per tenant:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: the first gradient as the program's optimizer got it
  (its first moment after one step over ``1 - b1``, with the ``b1`` the
  program's optimizer states), leaf by leaf: the gap
  between the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and the median leaf's; the worst leaf;
* ``change_gap``: the same measure for the change of the parameters after
  three steps.

A leaf whose reference gradient is nought to rounding (under a thousandth of
the median leaf's on every step compared) moves under Adam by round-off
alone and is left out of ``change_gap``; a leaf whose first reference
gradient is so small (LoRA ``a`` and the adapter's ``down`` have none while
their partner starts at zero) is left out of ``grad_gap``.

Each number has its limit in ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NEGLIGIBLE = 1e-3


def _leaves(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_leaves(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = np.asarray(v, np.float64)
    return out


def _norms(trees: List[Dict]) -> Dict[str, float]:
    """Norm of every leaf of every tenant, keyed ``tenant/site/leaf``."""
    out = {}
    for i, t in enumerate(trees):
        for k, v in _leaves(t).items():
            out[f"{i}/{k}"] = float(np.linalg.norm(v))
    return out


def _worst_gap(got: Dict[str, float], want: Dict[str, float], keep) -> float:
    keys = [k for k in want if keep(k)]
    if not keys:
        return 0.0
    med = float(np.median([want[k] for k in keys]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in keys)


def training_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"losses": [tenant][step], "grads": [tenant tree]
    of the first gradient, "change": [tenant tree] after three steps,
    and for ``ref`` also "grad_norms": [step][key->norm] of every step}."""
    pl = np.asarray(prog["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(pl - rl) / np.abs(rl)))
    g_ref = _norms(ref["grads"])
    g_prog = _norms(prog["grads"])
    steps = ref["grad_norms"]
    med = [float(np.median(list(s.values()))) for s in steps]
    first = lambda k: steps[0][k] >= NEGLIGIBLE * med[0]          # noqa: E731
    moves = lambda k: any(s[k] >= NEGLIGIBLE * m                   # noqa: E731
                          for s, m in zip(steps, med))
    c_ref = _norms(ref["change"])
    c_prog = _norms(prog["change"])
    return {"loss_gap": loss_gap,
            "grad_gap": _worst_gap(g_prog, g_ref, first),
            "change_gap": _worst_gap(c_prog, c_ref, moves)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit, and every limit given a number."""
    missing = [k for k in limits if k not in numbers]
    if missing:
        raise KeyError(f"no reading for limited numbers {missing}")
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
