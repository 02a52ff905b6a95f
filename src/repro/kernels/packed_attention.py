"""Packed flash attention kernel (TPU Pallas) — §3.5 alignment consumer.

Flash attention with *segment-id* masking so chunk-packed batches (multiple
original sequences packed per row) never attend across sequence boundaries —
the paper's "wasted attention computation across sequences" is eliminated
structurally.  Causal + segment masks; GQA by indexing the KV head as
``h // group`` in the BlockSpec index maps.

Grid: (batch*heads, n_q, n_k), n_k innermost so the online-softmax scratch
(m, l, acc) carries across KV tiles of one Q tile.  Fully-masked KV tiles
(j beyond the causal frontier) are skipped with ``pl.when`` — on TPU the
block still iterates but skips the MXU work, which is the grid-pruning
analogue of flash attention's triangular traversal.

Layout: the kernels see head-major ``[B, H, S, dh]`` operands (the wrapper
transposes the model's ``[B, S, H, dh]``), so every block's last two dims
are ``(block, dh)`` with ``dh`` the whole array dim — the TPU's (8, 128)
tiling rule.  Per-row vectors follow the same rule: the query side is a
column ``[B, S, 1]`` (block ``(1, block_q, 1)``), the key side a row
``[B, 1, Sk]`` (block ``(1, 1, block_k)``), and the saved logsumexp a
column ``[B, H, S, 1]``, so masks and softmax statistics are 2D broadcasts
of ``(block_q, 1)`` against ``(1, block_k)``.

Differentiable via ``jax.custom_vjp`` (flash-attention backward).  The
forward under autodiff additionally emits the per-row logsumexp
L = m + log(l) ([B, H, S, 1] f32), so the backward never materializes the
[S, S] probability matrix: each tile recomputes p = exp(q k^T / sqrt(d) - L)
from the saved L.  Two backward kernels mirror the forward traversal:

  * dq  — grid (B*H, n_q, n_k), KV innermost; accumulates
          dq += (p ∘ (do v^T - D)) k / sqrt(d) in VMEM scratch.
  * dkv — grid (B*H, n_k, n_q), Q innermost; accumulates per-QUERY-head
          dk/dv tiles (dv += p^T do; dk += (p ∘ (do v^T - D))^T q / sqrt(d));
          GQA group-sum over the G query heads of each KV head happens
          outside the kernel so every output block is written exactly once
          (no output-revisiting hazards across the bh grid dim).

The same causal-frontier tile pruning applies in both directions, and rows
that are fully masked (possible in padded packed batches) carry a sentinel
L = +1e30 so their p underflows to exactly zero in the backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, SUBLANE, fit_block

NEG_INF = -1e30
LSE_MASKED = 1e30  # logsumexp sentinel for fully-masked rows


def _tile_mask(qpos, kpos, qseg, kseg, causal):
    """(block_q, block_k) visibility from q-side columns and k-side rows."""
    # wildcard k rows: kseg == -1 matches EVERY query segment (learned
    # prefix-tuning k/v rows, gated per batch row); any other negative kseg
    # matches none (prefix rows of tasks the row does not belong to)
    mask = (qseg == kseg) | (kseg == -1)
    if causal:
        mask &= qpos >= kpos
    return mask


def _rows(qpos_ref, kpos_ref, qseg_ref, kseg_ref):
    # q side: (block_q, 1) columns; k side: (1, block_k) rows
    return qpos_ref[0], kpos_ref[0], qseg_ref[0], kseg_ref[0]


def _scores(q_ref, k_ref, scale):
    return jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [block_q, block_k]


def _frontier(i, j, causal, block_q, block_k, k_offset):
    """False for tiles strictly above the causal diagonal band
    (``k_offset`` = leading always-visible k rows, e.g. learned prefixes)."""
    if not causal:
        return jnp.asarray(True)
    return j * block_k <= (i + 1) * block_q - 1 + k_offset


def _fwd_kernel(
    q_ref,    # [1, 1, block_q, dh]
    k_ref,    # [1, 1, block_k, dh]
    v_ref,    # [1, 1, block_k, dh]
    qpos_ref,  # [1, block_q, 1]
    kpos_ref,  # [1, 1, block_k]
    qseg_ref,  # [1, block_q, 1]
    kseg_ref,  # [1, 1, block_k]
    o_ref,    # [1, 1, block_q, dh]
    *rest,    # (lse_ref? [1, 1, block_q, 1], m_ref, l_ref, acc_ref)
    n_k: int,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    save_lse: bool,
    k_offset: int = 0,
):
    m_ref, l_ref, acc_ref = rest[-3:]
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_frontier(i, j, causal, block_q, block_k, k_offset))
    def _tile():
        s = _scores(q_ref, k_ref, scale)
        s = jnp.where(_tile_mask(*_rows(qpos_ref, kpos_ref, qseg_ref,
                                        kseg_ref), causal), s, NEG_INF)
        m_prev = m_ref[...]                                   # [block_q, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if save_lse:
            m = m_ref[...]
            rest[0][0, 0] = jnp.where(
                m > NEG_INF * 0.5, m + jnp.log(jnp.maximum(l_ref[...], 1e-30)),
                LSE_MASKED,
            )


def _probs(q_ref, k_ref, rows, lse_ref, causal, scale):
    s = _scores(q_ref, k_ref, scale)
    return jnp.where(_tile_mask(*rows, causal), jnp.exp(s - lse_ref[0, 0]), 0.0)


def _dq_kernel(
    q_ref, k_ref, v_ref,
    qpos_ref, kpos_ref, qseg_ref, kseg_ref,
    do_ref,   # [1, 1, block_q, dh]
    o_ref,    # [1, 1, block_q, dh]
    lse_ref,  # [1, 1, block_q, 1]
    dq_ref,   # [1, 1, block_q, dh]
    d_ref,    # [block_q, 1] f32 scratch (D = rowsum(do * o))
    dq_acc,   # [block_q, dh] f32 scratch
    *,
    n_k: int,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    k_offset: int = 0,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        d_ref[...] = (do * o).sum(axis=-1, keepdims=True)
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_frontier(i, j, causal, block_q, block_k, k_offset))
    def _tile():
        p = _probs(q_ref, k_ref, _rows(qpos_ref, kpos_ref, qseg_ref, kseg_ref),
                   lse_ref, causal, scale)
        dp = jax.lax.dot_general(
            do_ref[0, 0].astype(jnp.float32), v_ref[0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        ds = p * (dp - d_ref[...]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_k - 1)
    def _emit():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref,
    qpos_ref, kpos_ref, qseg_ref, kseg_ref,
    do_ref, o_ref, lse_ref,
    dk_ref,   # [1, 1, block_k, dh] (per query head; group-summed outside)
    dv_ref,   # [1, 1, block_k, dh]
    dk_acc,   # [block_k, dh] f32 scratch
    dv_acc,   # [block_k, dh] f32 scratch
    *,
    n_q: int,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    k_offset: int = 0,
):
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_frontier(i, j, causal, block_q, block_k, k_offset))
    def _tile():
        do = do_ref[0, 0].astype(jnp.float32)
        p = _probs(q_ref, k_ref, _rows(qpos_ref, kpos_ref, qseg_ref, kseg_ref),
                   lse_ref, causal, scale)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        d = (do * o_ref[0, 0].astype(jnp.float32)).sum(axis=-1, keepdims=True)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - d) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q_ref[0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_q - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _specs(H, G, block_q, block_k, dh, *, kv_major):
    """Common BlockSpecs.  Grid is (bh, i, j) fwd/dq or (bh, j, i) dkv;
    ``kv_major`` only flips which grid position is the Q-tile index."""

    def ij(a, b):
        return (b, a) if kv_major else (a, b)

    def qi(bh, a, b):
        return (bh // H, bh % H, ij(a, b)[0], 0)

    def kj(bh, a, b):
        return (bh // H, (bh % H) // G, ij(a, b)[1], 0)

    def rq(bh, a, b):
        return (bh // H, ij(a, b)[0], 0)

    def rk(bh, a, b):
        return (bh // H, 0, ij(a, b)[1])

    return {
        "q": pl.BlockSpec((1, 1, block_q, dh), qi),
        "k": pl.BlockSpec((1, 1, block_k, dh), kj),
        "rowq": pl.BlockSpec((1, block_q, 1), rq),
        "rowk": pl.BlockSpec((1, 1, block_k), rk),
        "lse": pl.BlockSpec((1, 1, block_q, 1), qi),
    }


def _row_operands(positions, segment_ids, k_positions, k_segment_ids):
    """q-side ids as [B, S, 1] columns, k-side ids as [B, 1, Sk] rows."""
    return (positions[:, :, None], k_positions[:, None, :],
            segment_ids[:, :, None], k_segment_ids[:, None, :])


def _fwd_call(q, k, v, positions, segment_ids, k_positions, k_segment_ids,
              causal, block_q, block_k, interpret, save_lse):
    B, H, S, dh = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    n_q, n_k = S // block_q, Sk // block_k
    sp = _specs(H, G, block_q, block_k, dh, kv_major=False)

    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [sp["q"]]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32))
        out_specs.append(sp["lse"])

    fn = pl.pallas_call(
        functools.partial(
            _fwd_kernel, n_k=n_k, causal=causal, scale=1.0 / np.sqrt(dh),
            block_q=block_q, block_k=block_k, save_lse=save_lse,
            k_offset=Sk - S,
        ),
        grid=(B * H, n_q, n_k),
        in_specs=[sp["q"], sp["k"], sp["k"],
                  sp["rowq"], sp["rowk"], sp["rowq"], sp["rowk"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )
    out = fn(q, k, v, *_row_operands(positions, segment_ids, k_positions,
                                     k_segment_ids))
    return out if save_lse else out[0]


def _bwd_call(q, k, v, positions, segment_ids, k_positions, k_segment_ids,
              o, lse, do, causal, block_q, block_k, interpret):
    B, H, S, dh = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    n_q, n_k = S // block_q, Sk // block_k
    scale = 1.0 / np.sqrt(dh)
    k_offset = Sk - S
    rows = _row_operands(positions, segment_ids, k_positions, k_segment_ids)

    sp = _specs(H, G, block_q, block_k, dh, kv_major=False)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, n_k=n_k, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, k_offset=k_offset,
        ),
        grid=(B * H, n_q, n_k),
        in_specs=[sp["q"], sp["k"], sp["k"],
                  sp["rowq"], sp["rowk"], sp["rowq"], sp["rowk"],
                  sp["q"], sp["q"], sp["lse"]],
        out_specs=sp["q"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *rows, do, o, lse)

    sp = _specs(H, G, block_q, block_k, dh, kv_major=True)
    # dk/dv are accumulated per QUERY head (block written once per (bh, j))
    # and group-summed to the Hkv axis outside the kernel.
    dkq_spec = pl.BlockSpec(
        (1, 1, block_k, dh), lambda bh, j, i: (bh // H, bh % H, j, 0)
    )
    dkq, dvq = pl.pallas_call(
        functools.partial(
            _dkv_kernel, n_q=n_q, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, k_offset=k_offset,
        ),
        grid=(B * H, n_k, n_q),
        in_specs=[sp["q"], sp["k"], sp["k"],
                  sp["rowq"], sp["rowk"], sp["rowq"], sp["rowk"],
                  sp["q"], sp["q"], sp["lse"]],
        out_specs=[dkq_spec, dkq_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *rows, do, o, lse)

    dk = dkq.reshape(B, Hkv, G, Sk, dh).sum(axis=2).astype(k.dtype)
    dv = dvq.reshape(B, Hkv, G, Sk, dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _packed_attention(q, k, v, positions, segment_ids, k_positions,
                      k_segment_ids, causal, block_q, block_k, interpret):
    return _fwd_call(q, k, v, positions, segment_ids, k_positions,
                     k_segment_ids, causal, block_q, block_k, interpret,
                     save_lse=False)


def _packed_attention_fwd(q, k, v, positions, segment_ids, k_positions,
                          k_segment_ids, causal, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, positions, segment_ids, k_positions,
                       k_segment_ids, causal, block_q, block_k, interpret,
                       save_lse=True)
    return o, (q, k, v, positions, segment_ids, k_positions, k_segment_ids,
               o, lse)


def _packed_attention_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, positions, segment_ids, k_positions, k_segment_ids, o, lse = res
    dq, dk, dv = _bwd_call(q, k, v, positions, segment_ids, k_positions,
                           k_segment_ids, o, lse, do, causal, block_q,
                           block_k, interpret)
    dpos = np.zeros(positions.shape, jax.dtypes.float0)
    dseg = np.zeros(segment_ids.shape, jax.dtypes.float0)
    dkpos = np.zeros(k_positions.shape, jax.dtypes.float0)
    dkseg = np.zeros(k_segment_ids.shape, jax.dtypes.float0)
    return dq, dk, dv, dpos, dseg, dkpos, dkseg


_packed_attention.defvjp(_packed_attention_fwd, _packed_attention_bwd)


def packed_attention_pallas(
    q: jax.Array,  # [B, S, H, dh]
    k: jax.Array,  # [B, Sk, Hkv, dh] (Sk >= S: leading rows may be prefixes)
    v: jax.Array,
    segment_ids: Optional[jax.Array] = None,  # [B, S]
    positions: Optional[jax.Array] = None,    # [B, S]
    causal: bool = True,
    *,
    k_segment_ids: Optional[jax.Array] = None,  # [B, Sk]; -1 = wildcard row
    k_positions: Optional[jax.Array] = None,    # [B, Sk]
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Packed flash attention; the k/v sequence may carry ``Sk - S`` extra
    leading rows (learned prefix-tuning k/v) with their own segment ids:
    ``k_segment_ids == -1`` marks a row visible to EVERY query of the batch
    row, any other negative value a row visible to none."""
    B, S, H, dh = q.shape
    Sk = k.shape[1]
    # q tiles are sublane blocks of the [.., S, dh] operands and columns of
    # the q-side ids; k tiles are also lane blocks of the k-side id rows
    block_q = fit_block(S, block_q, SUBLANE)
    block_k = fit_block(Sk, block_k, LANE)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if segment_ids is None:
        segment_ids = jnp.zeros((B, S), jnp.int32)
    if k_positions is None:
        assert Sk == S, "k-side positions required when Sk != S"
        k_positions = positions
    if k_segment_ids is None:
        assert Sk == S, "k-side segment ids required when Sk != S"
        k_segment_ids = segment_ids
    head_major = (0, 2, 1, 3)
    o = _packed_attention(
        q.transpose(head_major), k.transpose(head_major),
        v.transpose(head_major), positions.astype(jnp.int32),
        segment_ids.astype(jnp.int32), k_positions.astype(jnp.int32),
        k_segment_ids.astype(jnp.int32), causal, block_q, block_k, interpret,
    )
    return o.transpose(head_major)
