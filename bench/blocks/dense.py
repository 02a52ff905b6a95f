"""The dense decoder block (Llama and StarCoder2 layouts): RMSNorm, rotary
embeddings on the two halves of each head, grouped-query causal attention
with optional biases, a SwiGLU or tanh-GELU MLP, a tied or untied LM head.

Its adapter sites are the four attention projections and the MLP's
matmuls, on every layer; the program stacks them ``[L, cap, ...]``, which is
the benchmark's own layout, so both adapter maps are the identity.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import weights as wt
import work

ATTN_TARGETS = ("attn_q", "attn_k", "attn_v", "attn_o")


# ---------------------------------------------------------------------------
# the program's configuration


def program_config(cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's sizes so that the program runs what the file says."""
    from repro.configs import get_config

    prog = cfg["program"]
    ac = get_config(prog["arch"]).with_overrides(**prog.get("overrides", {}))
    if ac.family != "dense" or ac.qk_norm:
        raise RuntimeError(f"program arch {prog['arch']!r} is of family "
                           f"{ac.family!r} (qk_norm {ac.qk_norm}), not the "
                           "file's dense block")
    want = {
        "num_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "gated_mlp": cfg["block"]["mlp"] == "swiglu",
        "attention_bias": cfg["block"]["attention_bias"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))),
    }
    got = {k: getattr(ac, k) for k in want}
    got["head_dim"] = ac.resolved_head_dim()
    want["head_dim"] = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if got != want:
        raise RuntimeError(f"program config {got} does not run the file's {want}")
    return ac


# ---------------------------------------------------------------------------
# sizes and sites


def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def _mlp_targets(cfg: Dict) -> tuple:
    if cfg["block"]["mlp"] == "swiglu":
        return ("mlp_gate", "mlp_up", "mlp_down")
    return ("mlp_fc1", "mlp_fc2")


def targets(cfg: Dict, group: str) -> tuple:
    """``"attn"``: the attention projections; ``"all"``: those and every
    MLP matmul the configuration has."""
    return {"attn": ATTN_TARGETS, "all": ATTN_TARGETS + _mlp_targets(cfg)}[group]


def site_dims(cfg: Dict) -> Dict[str, tuple]:
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


# ---------------------------------------------------------------------------
# weights


def backbone_shapes(cfg: Dict) -> Dict[str, tuple]:
    """Each per-layer leaf's shape, without the layer axis."""
    n = dims(cfg)
    d, H, Hkv, dh, F = n["d"], n["H"], n["Hkv"], n["dh"], n["F"]
    s = {"ln1": (d,), "wq": (d, H, dh), "wk": (d, Hkv, dh), "wv": (d, Hkv, dh),
         "wo": (H, dh, d), "ln2": (d,)}
    if cfg["block"]["attention_bias"]:
        s.update({"bq": (H, dh), "bk": (Hkv, dh), "bv": (Hkv, dh), "bo": (d,)})
    if cfg["block"]["mlp"] == "swiglu":
        s.update({"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)})
    else:
        s.update({"w_fc1": (d, F), "w_fc2": (F, d)})
    return s


def backbone(cfg: Dict, seed: int) -> Dict:
    """The frozen backbone's leaves from the seed, bfloat16 (traced inside
    ``weights.make_backbone``'s jitted call)."""
    n = dims(cfg)
    out = {"tok": (0.02 * jax.random.normal(wt._key(seed, "tok"),
                                            (n["V"], n["d"]))).astype(wt.BF16),
           "final_norm": (1.0 + 0.1 * jax.random.normal(
               wt._key(seed, "final_norm"), (n["d"],))).astype(wt.BF16),
           "layers": {}}
    if not cfg["tie_word_embeddings"]:
        out["unembed"] = (0.02 * jax.random.normal(
            wt._key(seed, "unembed"), (n["d"], n["V"]))).astype(wt.BF16)
    for name, shp in backbone_shapes(cfg).items():
        norm = name in ("ln1", "ln2")
        out["layers"][name] = wt._layers(
            wt._key(seed, name), n["L"], shp, 0.1 if norm else 0.02,
            1.0 if norm else 0.0)
    return out


def program_backbone(cfg: Dict, bb: Dict) -> Dict:
    """The backbone in the program's parameter tree layout."""
    lay = bb["layers"]
    attn = {"w_q": lay["wq"], "w_k": lay["wk"], "w_v": lay["wv"], "w_o": lay["wo"]}
    if "bq" in lay:
        attn.update({"b_q": lay["bq"], "b_k": lay["bk"], "b_v": lay["bv"],
                     "b_o": lay["bo"]})
    mlp = {k: lay[k] for k in ("w_gate", "w_up", "w_down", "w_fc1", "w_fc2")
           if k in lay}
    embed = {"tok": bb["tok"]}
    if "unembed" in bb:
        embed["unembed"] = bb["unembed"]
    return {"embed": embed, "final_norm": {"w": bb["final_norm"]},
            "layers": {"ln1": {"w": lay["ln1"]}, "attn": attn,
                       "ln2": {"w": lay["ln2"]}, "mlp": mlp}}


# ---------------------------------------------------------------------------
# adapters


def adapter_shapes(cfg: Dict, t, prefix_rows: int) -> Dict[str, Dict]:
    """A tenant's leaves per site at its own rank, ``[L, ...]``.  A prefix
    tenant has ``prefix_rows`` rows, the width of its stack."""
    n = dims(cfg)
    L = n["L"]
    if t.method == "prefix":
        kv = n["Hkv"] * n["dh"]
        return {"attn_prefix": {"pk": (L, prefix_rows, kv),
                                "pv": (L, prefix_rows, kv)}}
    sd = site_dims(cfg)
    out = {}
    for site in t.targets:
        din, dout = sd[site]
        if t.method == "lora":
            out[site] = {"a": (L, din, t.rank), "b": (L, t.rank, dout)}
        else:  # bottleneck adapter on the site's output
            out[site] = {"down": (L, dout, t.rank), "up": (L, t.rank, dout)}
    return out


def from_program(cfg: Dict, tree):
    """The program's adapter tree as per-site stacks: the same tree."""
    return tree


def to_program(cfg: Dict, stacks):
    """Per-site stacks as the program's adapter tree: the same tree."""
    return stacks


# ---------------------------------------------------------------------------
# the reference's block


def layer(ref, x, lw, ad, pos, kmask, method, scale, prefix):
    """One block over ``x [N, S, d]``.  ``kmask [N, S]`` marks the real
    tokens (keys); attention is causal within each row.  ``prefix`` is
    this layer's (pk, pv) [P, kv] or None."""
    n = ref.n
    N, S, _ = x.shape
    h = ref._rms(x, lw["ln1"])
    q = ref._mm("nsd,dhk->nshk", h, lw["wq"])
    k = ref._mm("nsd,dhk->nshk", h, lw["wk"])
    v = ref._mm("nsd,dhk->nshk", h, lw["wv"])
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = ref._site(ad, "attn_q", h, q, method, scale)
    k = ref._site(ad, "attn_k", h, k, method, scale)
    v = ref._site(ad, "attn_v", h, v, method, scale)
    q, k = ref._rope(q, pos), ref._rope(k, pos)
    G = n["H"] // n["Hkv"]
    qg = q.reshape(N, S, n["Hkv"], G, n["dh"])
    s = ref._mm("nskgd,ntkd->nkgst", qg, k) / np.sqrt(n["dh"])
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    allow = causal[None] & kmask[:, None, :]
    s = jnp.where(allow[:, None, None], s, -jnp.inf)
    if prefix is not None:
        P = prefix[0].shape[0]
        pk = prefix[0].reshape(P, n["Hkv"], n["dh"])
        pv = prefix[1].reshape(P, n["Hkv"], n["dh"])
        sp = ref._mm("nskgd,pkd->nkgsp", qg, pk) / np.sqrt(n["dh"])
        w = jax.nn.softmax(jnp.concatenate([sp, s], axis=-1), axis=-1)
        o = (ref._mm("nkgst,ntkd->nskgd", w[..., P:], v)
             + ref._mm("nkgsp,pkd->nskgd", w[..., :P], pv))
    else:
        w = jax.nn.softmax(s, axis=-1)
        o = ref._mm("nkgst,ntkd->nskgd", w, v)
    o = o.reshape(N, S, n["H"] * n["dh"])
    y = ref._mm("nsq,qd->nsd", o, lw["wo"].reshape(n["H"] * n["dh"], -1))
    if "bo" in lw:
        y = y + lw["bo"].astype(jnp.float32)
    y = ref._site(ad, "attn_o", o, y, method, scale)
    x = x + y
    h = ref._rms(x, lw["ln2"])
    if ref.cfg["block"]["mlp"] == "swiglu":
        g = ref._site(ad, "mlp_gate", h, ref._mm("nsd,df->nsf", h, lw["w_gate"]),
                      method, scale)
        u = ref._site(ad, "mlp_up", h, ref._mm("nsd,df->nsf", h, lw["w_up"]),
                      method, scale)
        m = jax.nn.silu(g) * u
        y = ref._site(ad, "mlp_down", m, ref._mm("nsf,fd->nsd", m, lw["w_down"]),
                      method, scale)
    else:
        f = ref._site(ad, "mlp_fc1", h, ref._mm("nsd,df->nsf", h, lw["w_fc1"]),
                      method, scale)
        m = jax.nn.gelu(f, approximate=True)
        y = ref._site(ad, "mlp_fc2", m, ref._mm("nsf,fd->nsd", m, lw["w_fc2"]),
                      method, scale)
    return x + y


def stack(ref, bb, x, ad, pos, kmask, method, scale):
    """Logits of the layers under ``lax.scan`` (each layer rematerialised),
    the final norm and the LM head."""
    site_ad = {k: v for k, v in ad.items() if k != "attn_prefix"}
    prefix = None
    if "attn_prefix" in ad:
        prefix = (ad["attn_prefix"]["pk"], ad["attn_prefix"]["pv"])

    @jax.checkpoint
    def body(x, xs):
        lw, a, pre = xs
        return layer(ref, x, lw, a, pos, kmask, method, scale, pre), None

    x, _ = jax.lax.scan(body, x, (bb["layers"], site_ad, prefix))
    x = ref._rms(x, bb["final_norm"])
    if "unembed" in bb:
        return ref._mm("nsd,dv->nsv", x, bb["unembed"])
    return ref._mm("nsd,vd->nsv", x, bb["tok"])


# ---------------------------------------------------------------------------
# work


def matmul_params(cfg: Dict) -> int:
    """Weights every token is multiplied by: the layers' projections and
    MLP, and the LM head (the tied embedding when the head is tied)."""
    n = dims(cfg)
    per_layer = sum(i * o for i, o in site_dims(cfg).values())
    return n["L"] * per_layer + n["d"] * n["V"]


def adapter_flops_fwd(cfg: Dict, method: str, rank: int, targets) -> float:
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
        total += 3.0 * attn_pair * work.attention_pairs(t.lengths, prefix)
        total += 3.0 * adapter_flops_fwd(cfg, t.method, t.rank, t.targets) * toks
    return total
