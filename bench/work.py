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
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence

def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def site_dims(cfg: Dict) -> Dict[str, tuple]:
    """(d_in, d_out) of every matmul an adapter can attach to."""
    n = dims(cfg)
    q, kv = n["H"] * n["dh"], n["Hkv"] * n["dh"]
    out = {"attn_q": (n["d"], q), "attn_k": (n["d"], kv),
           "attn_v": (n["d"], kv), "attn_o": (q, n["d"])}
    if cfg["block"]["mlp"] == "swiglu":
        out.update({"mlp_gate": (n["d"], n["F"]), "mlp_up": (n["d"], n["F"]),
                    "mlp_down": (n["F"], n["d"])})
    else:
        out.update({"mlp_fc1": (n["d"], n["F"]), "mlp_fc2": (n["F"], n["d"])})
    return out


def matmul_params(cfg: Dict) -> int:
    """Weights every token is multiplied by: the layers' projections and
    MLP, and the LM head (the tied embedding when the head is tied)."""
    n = dims(cfg)
    per_layer = sum(i * o for i, o in site_dims(cfg).values())
    return n["L"] * per_layer + n["d"] * n["V"]


def attention_pairs(lengths: Iterable[int], prefix_rows: int = 0) -> int:
    """Causal (query, key) pairs of a set of sequences, plus every query's
    prefix rows."""
    return sum(l * (l + 1) // 2 + l * prefix_rows for l in lengths)


def adapter_flops_fwd(cfg: Dict, method: str, rank: int,
                      targets: Sequence[str]) -> float:
    """Forward FLOPs per token of one tenant's adapter over all layers
    (prefix rows are counted as attention pairs instead)."""
    if method == "prefix":
        return 0.0
    sd = site_dims(cfg)
    per = 0.0
    for s in targets:
        din, dout = sd[s]
        per += 2.0 * rank * (din + dout) if method == "lora" else 4.0 * rank * dout
    return dims(cfg)["L"] * per


def train_flops(cfg: Dict, tenants) -> float:
    """Required FLOPs of one training iteration: every tenant's frozen
    lengths (one micro-batch each), forward plus backward."""
    n = dims(cfg)
    attn_pair = 4.0 * n["dh"] * n["H"] * n["L"]
    total = 0.0
    for t in tenants:
        toks = sum(t.lengths)
        prefix = t.rank if t.method == "prefix" else 0
        total += 4.0 * matmul_params(cfg) * toks
        total += 3.0 * attn_pair * attention_pairs(t.lengths, prefix)
        total += 3.0 * adapter_flops_fwd(cfg, t.method, t.rank, t.targets) * toks
    return total
