"""Attention: GQA with RoPE/M-RoPE, memory-O(S) flash formulations, decode.

Two training-time flash formulations, selected by the sharding plan:

* ``pairs``  — scan over lower-triangular (q-block, kv-block) pairs, exact
  causal FLOPs.  Used when attention is head-sharded TP ("heads" mode): the
  sequence dim of the carry is unsharded, so per-block dynamic updates stay
  local.
* ``kvscan`` — scan over kv blocks updating all q blocks with an exact
  causal mask.  GSPMD-clean when q is sequence-sharded over the model axis
  (context-parallel "cp" mode).  Counts ~2x causal FLOPs in HLO (masked
  upper triangle is still computed); the §Perf hillclimb replaces it with a
  shard_map striped-CP variant for the chosen cells.

Both support segment ids (chunk-packed batches from §3.5 alignment).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.distributed.sharding import shard
from repro.models.layers import ParamSpec, apply_mrope, apply_rope, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attention_spec(cfg: ArchConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim()
    s = {
        "w_q": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "w_k": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "w_v": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "w_o": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.attention_bias:
        s["b_q"] = ParamSpec((h, dh), ("heads", "head_dim"), init="zeros")
        s["b_k"] = ParamSpec((hkv, dh), ("kv_heads", "head_dim"), init="zeros")
        s["b_v"] = ParamSpec((hkv, dh), ("kv_heads", "head_dim"), init="zeros")
        s["b_o"] = ParamSpec((d,), ("embed",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((dh,), ("head_dim",), init="ones")
        s["k_norm"] = ParamSpec((dh,), ("head_dim",), init="ones")
    return s


# ---------------------------------------------------------------------------
# Flash attention, "pairs" variant (exact causal FLOPs)
# ---------------------------------------------------------------------------


def _prescale_q(q: jax.Array, cfg: ArchConfig) -> jax.Array:
    """``q`` rescaled so that a path whose softmax scale is fixed at
    ``1 / sqrt(dh)`` (decode, striped context parallelism) applies the
    configuration's scale."""
    if not cfg.attention_scale:
        return q
    dh = q.shape[-1]
    return (q.astype(jnp.float32) * (cfg.attention_scale * np.sqrt(dh))).astype(q.dtype)


def _block_pairs(n_q: int, n_k: int, causal: bool, ratio: int) -> np.ndarray:
    """Static (i, j) block pair list; for causal, j*kb <= end of q block i."""
    pairs = []
    for i in range(n_q):
        for j in range(n_k):
            if not causal or j <= (i + 1) * ratio - 1:
                pairs.append((i, j))
    return np.asarray(pairs, dtype=np.int32)



def _fit_block(S: int, want: int) -> int:
    """Largest divisor of S that is <= want (block sizes must tile S)."""
    want = max(1, min(want, S))
    if S % want == 0:
        return want
    best = 1
    d = 1
    while d * d <= S:
        if S % d == 0:
            if d <= want:
                best = max(best, d)
            if S // d <= want:
                best = max(best, S // d)
        d += 1
    return best


def _prefix_carry(q5, kv_prefix, scale):
    """Initial online-softmax carry from learned prefix k/v rows (§3.2 real
    prefix-tuning): every query attends the gated prefix rows regardless of
    causal position or packed segment, so the prefix contribution is exactly
    an extra (always-visible) kv block folded in before the scan."""
    pk, pv, keep = kv_prefix  # [B, P, Hkv, dh], [B, P, Hkv, dh], [B, P]
    s = jnp.einsum("bskgd,bpkd->bskgp", q5.astype(jnp.float32),
                   pk.astype(jnp.float32)) * scale
    live = (keep > 0)[:, None, None, None, :]
    s = jnp.where(live, s, NEG_INF)
    m0 = s.max(axis=-1)                       # [B, S, Hkv, G]
    p = jnp.where(live, jnp.exp(s - m0[..., None]), 0.0)
    l0 = p.sum(axis=-1)
    o0 = jnp.einsum("bskgp,bpkd->bskgd", p, pv.astype(jnp.float32))
    return o0, m0, l0


def flash_attention_pairs(
    q: jax.Array,  # [B, S, H, dh]
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,
    *,
    block: int,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S]
    positions: Optional[jax.Array] = None,  # [B, S] (packed: within-segment)
    kv_prefix=None,  # (pk [B,P,Hkv,dh], pv [B,P,Hkv,dh], keep [B,P])
    scale: float,  # softmax scale (``ArchConfig.softmax_scale``)
) -> jax.Array:
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    blk = _fit_block(S, block)
    n = S // blk

    qb = q.reshape(B, n, blk, Hkv, G, dh)
    kb = k.reshape(B, n, blk, Hkv, dh)
    vb = v.reshape(B, n, blk, Hkv, dh)
    segb = segment_ids.reshape(B, n, blk) if segment_ids is not None else None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    posb = positions.reshape(B, n, blk)

    if kv_prefix is not None:
        q5 = q.reshape(B, S, Hkv, G, dh)
        o0, m0, l0 = _prefix_carry(q5, kv_prefix, scale)
        o = o0.reshape(B, n, blk, Hkv, G, dh)
        m = m0.reshape(B, n, blk, Hkv, G)
        l = l0.reshape(B, n, blk, Hkv, G)
    else:
        o = jnp.zeros((B, n, blk, Hkv, G, dh), jnp.float32)
        m = jnp.full((B, n, blk, Hkv, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, n, blk, Hkv, G), jnp.float32)

    pairs = jnp.asarray(_block_pairs(n, n, causal, 1))

    def step(carry, pair):
        o, m, l = carry
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qb, i, axis=1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
        s = jnp.einsum("bqkgd,bpkd->bqkgp", qi, kj, preferred_element_type=jnp.float32)
        s = s * scale  # [B, blk_q, Hkv, G, blk_k]
        mask = jnp.ones((B, blk, blk), bool)
        if causal:
            qpos = jax.lax.dynamic_index_in_dim(posb, i, axis=1, keepdims=False)
            kpos = jax.lax.dynamic_index_in_dim(posb, j, axis=1, keepdims=False)
            mask &= qpos[:, :, None] >= kpos[:, None, :]
        if segb is not None:
            sq = jax.lax.dynamic_index_in_dim(segb, i, axis=1, keepdims=False)
            sk = jax.lax.dynamic_index_in_dim(segb, j, axis=1, keepdims=False)
            mask &= sq[:, :, None] == sk[:, None, :]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)

        mi = jax.lax.dynamic_index_in_dim(m, i, axis=1, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, axis=1, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, axis=1, keepdims=False)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(mi - m_new)
        l_new = li * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bqkgp,bpkd->bqkgd", p, vj.astype(jnp.float32))
        o_new = oi * alpha[..., None] + pv

        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, axis=1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=1)
        return (o, m, l), None

    from repro.models.flags import cost_unroll

    (o, m, l), _ = jax.lax.scan(step, (o, m, l), pairs, unroll=cost_unroll())
    out = o / jnp.maximum(l[..., None], 1e-20)
    return out.reshape(B, S, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention, "kvscan" variant (CP/GSPMD-friendly)
# ---------------------------------------------------------------------------


def flash_attention_kvscan(
    q: jax.Array,  # [B, S, H, dh]  (seq may be sharded)
    k: jax.Array,  # [B, Sk, Hkv, dh] (replicated/gathered)
    v: jax.Array,
    *,
    kv_block: int,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,  # [B, S]
    kv_prefix=None,  # (pk [B,P,Hkv,dh], pv [B,P,Hkv,dh], keep [B,P])
    scale: float,  # softmax scale (``ArchConfig.softmax_scale``)
) -> jax.Array:
    B, S, H, dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    blk = _fit_block(Sk, kv_block)
    n = Sk // blk

    q5 = q.reshape(B, S, Hkv, G, dh)
    kb = k.reshape(B, n, blk, Hkv, dh)
    vb = v.reshape(B, n, blk, Hkv, dh)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        k_positions = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32), (B, Sk))
    else:
        assert S == Sk, "packed positions require self-attention (S == Sk)"
        k_positions = positions
    kposb = k_positions.reshape(B, n, blk)
    segb = segment_ids.reshape(B, n, blk) if segment_ids is not None else None

    if kv_prefix is not None:
        o, m, l = _prefix_carry(q5, kv_prefix, scale)
    else:
        o = jnp.zeros((B, S, Hkv, G, dh), jnp.float32)
        m = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, S, Hkv, G), jnp.float32)

    def step(carry, j):
        o, m, l = carry
        kj = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
        s = jnp.einsum("bqkgd,bpkd->bqkgp", q5, kj, preferred_element_type=jnp.float32)
        s = s * scale  # [B, S, Hkv, G, blk]
        kpos = jax.lax.dynamic_index_in_dim(kposb, j, axis=1, keepdims=False)
        mask = jnp.ones((B, S, blk), bool)
        if causal:
            mask &= positions[:, :, None] >= kpos[:, None, :]
        if segb is not None:
            sk = jax.lax.dynamic_index_in_dim(segb, j, axis=1, keepdims=False)
            mask &= segment_ids[:, :, None] == sk[:, None, :]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bqkgp,bpkd->bqkgd", p, vj.astype(jnp.float32))
        o_new = o * alpha[..., None] + pv
        return (o_new, m_new, l_new), None

    from repro.models.flags import cost_unroll

    (o, m, l), _ = jax.lax.scan(step, (o, m, l), jnp.arange(n), unroll=cost_unroll())
    out = o / jnp.maximum(l[..., None], 1e-20)
    return out.reshape(B, S, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one query token over a KV cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,  # [B, 1, H, dh]
    k_cache: jax.Array,  # [B, Smax, Hkv, dh]
    v_cache: jax.Array,
    cache_len: jax.Array,  # [] or [B] int32 — end of the valid window
    cache_start: Optional[jax.Array] = None,  # [] or [B] int32 — window start
) -> jax.Array:
    """One query token over a KV cache window ``[cache_start, cache_len)``.

    Per-row bounds support the fused multi-task decode pool: each batch row
    is an independent request at its own context length, and rows whose task
    has no folded prefix mask the cache's reserved prefix region out via
    ``cache_start`` (see :func:`init_kv_cache`).  Empty windows
    (``cache_len == cache_start``) yield zeros, not NaN: the softmax
    denominator is clamped like the flash paths.

    Dispatches through :mod:`repro.kernels.ops` like every other hot op —
    the xla tier is the dense reference, the Pallas tiers run the
    flash-decode split-KV kernel that reads each KV element once."""
    from repro.kernels import ops as kops

    return kops.decode_attention(q, k_cache, v_cache, cache_len, cache_start)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + flash / decode)
# ---------------------------------------------------------------------------


def _project_qkv(p, x, cfg: ArchConfig, positions, mrope_positions):
    from repro.peft.hooks import apply_base_op

    q = apply_base_op("attn_q", x, p["w_q"], "bsd,dhk->bshk", bias=p.get("b_q"))
    k = apply_base_op("attn_k", x, p["w_k"], "bsd,dhk->bshk", bias=p.get("b_k"))
    v = apply_base_op("attn_v", x, p["w_v"], "bsd,dhk->bshk", bias=p.get("b_v"))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.attention != "none" and cfg.positional == "rope" and positions is not None:
        if cfg.mrope and mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,  # [B, S, d]
    cfg: ArchConfig,
    *,
    mode: str = "pairs",  # pairs | kvscan
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    return_kv: bool = False,
) -> jax.Array:
    """``return_kv=True`` additionally returns the post-RoPE (k, v) rows —
    the prefill path captures them into the decode KV cache so a served
    prompt is processed in ONE chunked forward instead of token-by-token."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions, mrope_positions)
    if kv_override is not None:
        k, v = kv_override
        # Cross-attention: q/kv lengths differ -> kvscan handles ragged Sk.
        out = flash_attention_kvscan(q, k, v, kv_block=cfg.attn_kv_block, causal=False,
                                     scale=cfg.softmax_scale())
        from repro.peft.hooks import apply_base_op
        y = apply_base_op("attn_o", out, p["w_o"], "bshk,hkd->bsd", bias=p.get("b_o"))
        return (y, (k, v)) if return_kv else y
    # Soft-prompt PEFT: the active adapter context may carry learned per-row
    # k/v prefix rows for this layer (real prefix-tuning, §3.2).
    from repro.peft.hooks import active_context

    adapter_ctx = active_context()
    prefix = adapter_ctx.attn_prefix() if adapter_ctx is not None else None
    if prefix is not None:
        pk, pv, keep = prefix  # [B, P, kv_dim] pair + [B, P] row gate
        hkv, dh_ = cfg.num_kv_heads, cfg.resolved_head_dim()
        P = pk.shape[1]
        prefix = (pk.reshape(B, P, hkv, dh_).astype(k.dtype),
                  pv.reshape(B, P, hkv, dh_).astype(v.dtype), keep)
    if mode == "striped_cp":
        # §Perf: exact-causal load-balanced CP (striped seq layout inputs);
        # prefix rows (soft-prompt PEFT) ride along via the CP-aware prefix
        # broadcast — replicated per rank, folded into the carry init.
        from repro.distributed.sharding import active_rules
        from repro.models.cp_attention import striped_cp_attention

        mesh, _ = active_rules()
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", "seq", None, None)
        v = shard(v, "batch", "seq", None, None)
        # block small enough that each rank sees >=4 kv chunks — otherwise
        # the triangular chunk scan degenerates to full-S masked compute
        P_sz = mesh.shape["model"] if (mesh and "model" in mesh.axis_names) else 1
        blk = max(min(cfg.attn_q_block, 256, S // (4 * P_sz)), 16)
        out = striped_cp_attention(
            _prescale_q(q, cfg), k, v, positions, segment_ids, mesh, axis="model", block=blk,
            kv_prefix=prefix,
        )
        out = shard(out, "batch", "seq", None, None)
    elif mode == "pairs":
        # Routed through kernels.ops so ``set_impl("pallas")`` swaps the
        # training hot path onto the Pallas packed-attention kernel
        # (forward AND backward via its custom_vjp); the default "xla"
        # impl dispatches right back to flash_attention_pairs below.
        from repro.kernels import ops as kops

        q = shard(q, "batch", None, "heads", None)
        k = shard(k, "batch", None, "kv_heads", None)
        v = shard(v, "batch", None, "kv_heads", None)
        out = kops.packed_attention(
            q, k, v, causal=causal, block_q=cfg.attn_q_block,
            segment_ids=segment_ids, positions=positions if causal else None,
            prefix_kv=prefix[:2] if prefix is not None else None,
            prefix_keep=prefix[2] if prefix is not None else None,
            scale=cfg.softmax_scale(),
        )
        out = shard(out, "batch", None, "heads", None)
    else:  # kvscan (CP): q stays seq-sharded, kv gathered
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", "kv_seq", None, None)
        v = shard(v, "batch", "kv_seq", None, None)
        out = flash_attention_kvscan(
            q, k, v, kv_block=cfg.attn_kv_block, causal=causal,
            segment_ids=segment_ids, positions=positions if causal else None,
            kv_prefix=prefix, scale=cfg.softmax_scale(),
        )
        out = shard(out, "batch", "seq", None, None)
    from repro.peft.hooks import apply_base_op

    y = apply_base_op("attn_o", out, p["w_o"], "bshk,hkd->bsd", bias=p.get("b_o"))
    if return_kv:
        return y, (k, v)
    return y


def attention_decode_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,  # [B, 1, d]
    cfg: ArchConfig,
    cache: Dict[str, jax.Array],  # {"k": [B,Smax,Hkv,dh], "v": ..., "len": []}
    *,
    mrope_positions: Optional[jax.Array] = None,
    update_cache: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode token over the KV cache.

    Adapters apply exactly as at train time: every projection routes through
    ``apply_base_op``, so the active adapter context's Dispatch/Aggregate
    rules (LoRA, DoRA, IA3, ... — whatever methods the fused rows carry) hit
    the decode token identically to a training token.  Prefix-tuning needs
    no context here at all: its learned k/v rows were folded into the
    cache's reserved prefix region at prefill/bind time (``init_kv_cache``),
    so ``decode_attention`` sees them as ordinary cache rows.

    Cache keys: ``len`` is the next write index ([] scalar for the legacy
    lockstep path, [B] for the per-row request pool); optional ``t`` is the
    REAL token count (RoPE position — differs from ``len`` when the cache
    layout reserves prefix rows); optional ``lo`` [B] is the per-row start
    of the valid window (masks the unused prefix region of rows whose task
    folded no prefix).
    """
    pos = cache["len"]  # [] or [B] int32: next cache write index
    t = cache.get("t", pos)  # [] or [B]: real-token count (RoPE position)
    lo = cache.get("lo")  # [B] window start, or None (whole cache valid)
    B = x.shape[0]
    positions = jnp.reshape(t, (-1, 1)).astype(jnp.int32)  # [1|B, 1]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, mrope_positions)
    if update_cache:
        if pos.ndim == 0:  # lockstep: one shared write index
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        else:  # per-row write index (fused request pool)
            rows = jnp.arange(B)
            wr = jnp.minimum(pos, cache["k"].shape[1] - 1)
            k_cache = cache["k"].at[rows, wr].set(k_new[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, wr].set(v_new[:, 0].astype(cache["v"].dtype))
        new_len = pos + 1
    else:  # cross-attention: cache fixed
        k_cache, v_cache, new_len = cache["k"], cache["v"], pos
    out = decode_attention(_prescale_q(q, cfg), k_cache, v_cache, new_len,
                           cache_start=lo)
    from repro.peft.hooks import apply_base_op

    y = apply_base_op("attn_o", out, p["w_o"], "bshk,hkd->bsd", bias=p.get("b_o"))
    new_cache = dict(cache)
    new_cache.update({"k": k_cache, "v": v_cache, "len": new_len})
    if "t" in cache:
        new_cache["t"] = t + (1 if update_cache else 0)
    return y, new_cache


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                  prefix_reserve: int = 0, per_row: bool = False):
    """ONE layer's KV cache in exactly the per-layer dict contract
    ``attention_decode_apply`` consumes: ``len`` is the next WRITE index
    (pre-offset by the prefix region), ``t`` the real-token/RoPE count,
    ``lo`` the valid-window start.  The stacked serving path builds its
    [L, ...] state via ``Model.init_decode_state`` (whose ``pos`` counts
    real tokens; ``decode_step`` derives these per-layer dicts from it) —
    this constructor is the single-layer reference of the layout.

    With ``prefix_reserve=P`` the cache grows ``P`` extra leading rows per
    sequence: prefix-tuning's learned k/v rows are written (right-aligned)
    into ``[P - p, P)`` at prefill/bind time, real tokens start at offset
    ``P``, and the per-row window ``[lo, len)`` exposes exactly the folded
    prefix plus the decoded context.  ``per_row=True`` makes ``len``/``t``
    per-row [B] vectors so independent requests decode fused in one batch.
    """
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()
    shp = (batch,) if per_row else ()
    cache = {
        "k": jnp.zeros((batch, prefix_reserve + max_len, hkv, dh), dtype),
        "v": jnp.zeros((batch, prefix_reserve + max_len, hkv, dh), dtype),
        "len": jnp.full(shp, prefix_reserve, jnp.int32),
    }
    if prefix_reserve or per_row:
        cache["t"] = jnp.zeros(shp, jnp.int32)
        cache["lo"] = jnp.full((batch,), prefix_reserve, jnp.int32)
    return cache
