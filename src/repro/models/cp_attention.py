"""Striped context-parallel flash attention (beyond-paper §Perf optimization).

The baseline "kvscan" CP attention computes the full S x S score grid with a
causal mask — 2x the useful FLOPs, and HLO cost shows it.  This variant:

 * lays the sequence out in *stripes*: global q/kv block g lives on model
   rank g % P (block-cyclic).  Per-rank causal work is then balanced
   (contiguous sharding would leave rank P-1 with P x rank 0's work), and
   positions/segment ids travel with the data, so RoPE, causal masks and
   packing are layout-transparent.
 * runs inside shard_map: KV (small for GQA) is all-gathered per rank, and a
   static lower-triangular (q-block, kv-chunk) pair scan — kv chunks of
   P blocks — touches only the causal triangle.  Over-compute is limited to
   the masked tail of each diagonal chunk (~blk*P/2 tokens per q block).
 * everything is static-shape lax.scan: reverse-mode AD works out of the
   box (all_gather transposes to psum_scatter).

FLOPs: ~S^2/2 per head total (vs S^2 for kvscan), balanced across ranks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def stripe_permutation(S: int, block: int, P_sz: int) -> np.ndarray:
    """Permutation mapping contiguous token order -> striped layout.

    Block g (of n = S/block) goes to rank g % P at local slot g // P; the
    striped array is the concatenation of rank slices.  Returns indices such
    that ``x_striped = x[..., perm, ...]``.
    """
    n = S // block
    assert n % P_sz == 0, (n, P_sz)
    order = []
    for r in range(P_sz):
        for j in range(n // P_sz):
            g = j * P_sz + r
            order.extend(range(g * block, (g + 1) * block))
    return np.asarray(order, np.int64)


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return inv


def _flash_ragged_pairs(
    q: jax.Array,    # [B, nq, blk, Hkv, G, dh]  local q blocks (striped)
    k: jax.Array,    # [B, nc, cblk, Hkv, dh]    full gathered kv chunks
    v: jax.Array,
    qpos: jax.Array,  # [B, nq, blk] global positions
    kpos: jax.Array,  # [B, nc, cblk]
    qseg: Optional[jax.Array],
    kseg: Optional[jax.Array],
    kv_prefix=None,  # (pk [B,P,Hkv,dh], pv [B,P,Hkv,dh], keep [B,P])
) -> jax.Array:
    B, nq, blk, Hkv, G, dh = q.shape
    nc, cblk = k.shape[1], k.shape[2]
    scale = 1.0 / np.sqrt(dh)
    pairs = np.asarray([(i, t) for i in range(nq) for t in range(i + 1)], np.int32)

    if kv_prefix is not None:
        # CP-aware prefix broadcast: the learned rows are replicated to every
        # rank (they are tiny — rank * kv_dim), each rank folds them into its
        # LOCAL q blocks' online-softmax carry.  Prefix rows are visible to
        # every query of the owning batch row regardless of causal position
        # or stripe placement, so the carry init is layout-transparent.
        from repro.models.attention import _prefix_carry

        q5 = q.reshape(B, nq * blk, Hkv, G, dh)
        o0, m0, l0 = _prefix_carry(q5, kv_prefix, scale)
        o = o0.reshape(B, nq, blk, Hkv, G, dh)
        m = m0.reshape(B, nq, blk, Hkv, G)
        l = l0.reshape(B, nq, blk, Hkv, G)
    else:
        o = jnp.zeros((B, nq, blk, Hkv, G, dh), jnp.float32)
        m = jnp.full((B, nq, blk, Hkv, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, nq, blk, Hkv, G), jnp.float32)

    def step(carry, pair):
        o, m, l = carry
        i, t = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(k, t, axis=1, keepdims=False)
        vt = jax.lax.dynamic_index_in_dim(v, t, axis=1, keepdims=False)
        s = jnp.einsum("bqkgd,bpkd->bqkgp", qi, kt, preferred_element_type=jnp.float32)
        s = s * scale
        qp = jax.lax.dynamic_index_in_dim(qpos, i, axis=1, keepdims=False)
        kp = jax.lax.dynamic_index_in_dim(kpos, t, axis=1, keepdims=False)
        mask = qp[:, :, None] >= kp[:, None, :]
        if qseg is not None:
            sq = jax.lax.dynamic_index_in_dim(qseg, i, axis=1, keepdims=False)
            sk = jax.lax.dynamic_index_in_dim(kseg, t, axis=1, keepdims=False)
            mask &= sq[:, :, None] == sk[:, None, :]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, axis=1, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, axis=1, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, i, axis=1, keepdims=False)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(mi - m_new)
        l_new = li * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bqkgp,bpkd->bqkgd", p, vt.astype(jnp.float32))
        o_new = oi * alpha[..., None] + pv
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, i, axis=1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=1)
        return (o, m, l), None

    (o, m, l), _ = jax.lax.scan(step, (o, m, l), jnp.asarray(pairs))
    return (o / jnp.maximum(l[..., None], 1e-20))


def striped_cp_attention(
    q: jax.Array,  # [B, S, H, dh]   STRIPED global layout, seq sharded on axis
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,
    positions: jax.Array,     # [B, S] global positions (striped layout)
    segment_ids: Optional[jax.Array],  # [B, S] or None
    mesh: Mesh,
    axis: str = "model",
    block: int = 256,
    kv_prefix=None,  # (pk [B,P,Hkv,dh], pv [B,P,Hkv,dh], keep [B,P])
) -> jax.Array:
    """Exact-causal, load-balanced CP attention over mesh axis ``axis``.

    ``kv_prefix`` carries soft-prompt PEFT's learned k/v rows: replicated
    along the CP axis (batch-sharded like q over the DP axes) and folded
    into each rank's local online-softmax carry before the triangular chunk
    scan — the CP-aware prefix broadcast of the serving-layer ROADMAP item.
    """
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        # single-device fallback: same math, no shard_map (tests)
        n = S // block
        q6 = q.reshape(B, n, block, Hkv, G, dh)
        k5 = k.reshape(B, n, block, Hkv, dh)
        v5 = v.reshape(B, n, block, Hkv, dh)
        qp = positions.reshape(B, n, block)
        sg0 = segment_ids if segment_ids is not None else jnp.zeros((B, S), jnp.int32)
        qs = sg0.reshape(B, n, block)
        o = _flash_ragged_pairs(q6, k5, v5, qp, qp, qs, qs, kv_prefix=kv_prefix)
        return o.reshape(B, S, H, dh).astype(q.dtype)
    P_sz = mesh.shape[axis]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    seg = segment_ids if segment_ids is not None else jnp.zeros((B, S), jnp.int32)

    def body(q_l, k_l, v_l, pos_l, seg_l, *prefix_args):
        # local: [B_loc, S/P, ...]
        B = q_l.shape[0]
        S_l = q_l.shape[1]
        nq = S_l // block
        kg = jax.lax.all_gather(k_l, axis, axis=1, tiled=True)   # [B, S, Hkv, dh]
        vg = jax.lax.all_gather(v_l, axis, axis=1, tiled=True)
        pg = jax.lax.all_gather(pos_l, axis, axis=1, tiled=True)  # [B, S]
        sg = jax.lax.all_gather(seg_l, axis, axis=1, tiled=True)
        # gathered layout = rank-major striped; chunk c of P*block tokens
        # contains global blocks {c (mod-P interleaved)} — positions carry
        # the truth, so chunk t covers global blocks with index ≡ any, but
        # crucially chunk t of the *gathered* array holds rank r's block j
        # at offset r*S_l + j*block.  Re-chunk by global block index:
        n = S // block
        # gathered index of global block g (rank g%P, local j=g//P):
        gather_idx = np.concatenate([
            np.arange(block) + (g % P_sz) * S_l + (g // P_sz) * block
            for g in range(n)
        ])
        kg = kg[:, gather_idx]
        vg = vg[:, gather_idx]
        pg = pg[:, gather_idx]
        sg = sg[:, gather_idx]
        nc = n // P_sz
        cblk = P_sz * block
        q6 = q_l.reshape(B, nq, block, Hkv, G, dh)
        k5 = kg.reshape(B, nc, cblk, Hkv, dh)
        v5 = vg.reshape(B, nc, cblk, Hkv, dh)
        qp = pos_l.reshape(B, nq, block)
        kp = pg.reshape(B, nc, cblk)
        qs = seg_l.reshape(B, nq, block)
        ks = sg.reshape(B, nc, cblk)
        pref = tuple(prefix_args) if prefix_args else None
        o = _flash_ragged_pairs(q6, k5, v5, qp, kp, qs, ks, kv_prefix=pref)
        return o.reshape(B, S_l, H, dh).astype(q_l.dtype)

    bspec = P(dp_axes if dp_axes else None, axis, None, None)
    pspec = P(dp_axes if dp_axes else None, axis)
    in_specs = [bspec, bspec, bspec, pspec, pspec]
    args = [q, k, v, positions, seg]
    if kv_prefix is not None:
        # prefix rows: batch-sharded with q, REPLICATED along the CP axis
        prow = P(dp_axes if dp_axes else None, None, None, None)
        pkeep = P(dp_axes if dp_axes else None, None)
        in_specs += [prow, prow, pkeep]
        args += list(kv_prefix)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=bspec,
        check_vma=False,
    )(*args)
