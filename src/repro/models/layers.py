"""Shared layer primitives: ParamSpec trees, norms, RoPE/M-RoPE, MLPs.

Parameters are declared as ``ParamSpec`` trees so the same declaration
drives (a) real initialization, (b) abstract ``ShapeDtypeStruct`` twins
for compiling without allocating, and (c) ``NamedSharding`` derivation
from logical axes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axes, len == rank
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.02
    dtype: str = "bfloat16"

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def is_spec_leaf(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def materialize(spec_tree, key: jax.Array):
    """Initialize a real param tree from a ParamSpec tree."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec_leaf)
    keys = jax.random.split(key, max(len(leaves), 1))

    def one(spec: ParamSpec, k):
        dt = jnp.dtype(spec.dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        if spec.init == "const":  # constant fill at ``scale`` (e.g. VeRA d=0.1)
            return jnp.full(spec.shape, spec.scale, dt)
        return (jax.random.normal(k, spec.shape, jnp.float32) * spec.scale).astype(dt)

    return jax.tree.unflatten(treedef, [one(s, k) for s, k in zip(leaves, keys)])


def abstract(spec_tree):
    return jax.tree.map(lambda s: s.abstract(), spec_tree, is_leaf=is_spec_leaf)


def spec_logical_axes(spec_tree):
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=is_spec_leaf)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + qwen2-vl 3-section M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, dh]; positions: broadcastable to [..., S] (int32)."""
    dh = x.shape[-1]
    inv = rope_freqs(dh, theta)  # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., S, dh/2]
    ang = ang[..., None, :]  # [..., S, 1, dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,  # [3, ..., S] — temporal / height / width ids
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the dh/2 frequency slots are split into
    (t, h, w) sections, each rotated by its own position channel."""
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, dh)
    inv = rope_freqs(dh, theta)  # [half]
    # Select which position channel drives each frequency slot.
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=half
    )  # [half] in {0,1,2}
    # positions: [3, ..., S] -> per-slot position [..., S, half]
    pos = jnp.moveaxis(positions, 0, -1).astype(jnp.float32)  # [..., S, 3]
    slot_pos = pos[..., sec_id]  # [..., S, half]
    ang = slot_pos * inv  # [..., S, half]
    ang = ang[..., None, :]  # head dim broadcast
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style sinusoidal table [length, dim]."""
    log_timescale = np.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_spec(d: int, ff: int, gated: bool, bias: bool = False) -> Dict[str, ParamSpec]:
    s: Dict[str, ParamSpec] = {}
    if gated:
        s["w_gate"] = ParamSpec((d, ff), ("embed", "ff"))
        s["w_up"] = ParamSpec((d, ff), ("embed", "ff"))
        s["w_down"] = ParamSpec((ff, d), ("ff", "embed"))
    else:
        s["w_fc1"] = ParamSpec((d, ff), ("embed", "ff"))
        s["w_fc2"] = ParamSpec((ff, d), ("ff", "embed"))
        if bias:
            s["b_fc1"] = ParamSpec((ff,), ("ff",), init="zeros")
            s["b_fc2"] = ParamSpec((d,), ("embed",), init="zeros")
    return s


def _mlp_hidden_axes() -> tuple:
    """FFN-hidden sharding: TP mode keeps "ff" on the model axis (caller
    gathers x, reduce-scatters out); FSDP mode ("ff" -> tuple of axes =
    weights sharded at rest, gathered just-in-time) computes fully
    seq-local, so the hidden stays sequence-sharded — no x gather, no RS."""
    from repro.distributed.sharding import active_rules

    _, rules = active_rules()
    if rules is not None and isinstance(rules.lookup("ff"), tuple):
        return ("batch", "seq", None)
    return ("batch", None, "ff")


def mlp_apply(p: Dict[str, jax.Array], x: jax.Array, gated: bool, prefix: str = "mlp") -> jax.Array:
    """x: [B, S, d] (replicated over model here — caller gathers/scatters)."""
    from repro.peft.hooks import apply_base_op

    h_axes = _mlp_hidden_axes()
    if gated:
        g = apply_base_op(f"{prefix}_gate", x, p["w_gate"], "bsd,df->bsf")
        u = apply_base_op(f"{prefix}_up", x, p["w_up"], "bsd,df->bsf")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        h = shard(h, *h_axes)
        return apply_base_op(f"{prefix}_down", h, p["w_down"], "bsf,fd->bsd")
    h = apply_base_op(f"{prefix}_fc1", x, p["w_fc1"], "bsd,df->bsf", bias=p.get("b_fc1"))
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, *h_axes)
    y = apply_base_op(f"{prefix}_fc2", h, p["w_fc2"], "bsf,fd->bsd", bias=p.get("b_fc2"))
    return y


# ---------------------------------------------------------------------------
# Embedding / unembedding (vocab-sharded)
# ---------------------------------------------------------------------------


def pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


def embed_spec(vocab: int, d: int, tie: bool) -> Dict[str, ParamSpec]:
    s = {"tok": ParamSpec((vocab, d), ("vocab", "embed"), scale=0.01)}
    if not tie:
        s["unembed"] = ParamSpec((d, vocab), ("embed", "vocab"), scale=0.01)
    return s


def embed_apply(p: Dict[str, jax.Array], tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed_apply(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    if "unembed" in p:
        return jnp.einsum("bsd,dv->bsv", x, p["unembed"])
    return jnp.einsum("bsd,vd->bsv", x, p["tok"])
