"""Chunked SSD / gated-linear-attention scan kernel (TPU Pallas).

Hot-spot for the zamba2/xlstm cells (incl. ``long_500k``): the recurrence
  H_t = exp(la_t) H_{t-1} + exp(li_t) k_t (x) v_t ;  y_t = q_t . H_t
is evaluated chunk-parallel — intra-chunk via a decay-masked block product
(two MXU matmuls per chunk) and inter-chunk via a VMEM-resident state that
carries across the innermost grid dimension.  This is the TPU re-think of
the Mamba2 SSD CUDA kernel: no warp-level shuffles, just grid-carried VMEM
state + MXU tiles.

Grid: (B*H, n_chunks); chunk dim innermost so the [dk, dv] f32 state scratch
persists across chunks of one (batch, head) program.

Layout: the kernels see head-major ``[B, H, S, d]`` operands (the wrapper
transposes the model's ``[B, S, H, d]``), so each chunk is a ``(Q, d)``
tile; the per-position log-decay, log-input and reset rows enter as
``(Q, 1)`` columns.  That is the TPU's (8, 128) block rule: the last two
block dims are sublane-aligned or the whole array dim.

The op is differentiable via ``jax.custom_vjp``.  The forward under autodiff
additionally spills the per-chunk ENTRY states H_in ([B*H, n, dk, dv] f32 —
one [dk, dv] tile per chunk, tiny next to q/k/v), so the backward never
replays the forward recurrence.  The backward is two kernels:

  1. Reverse decay-cumsum kernel: the inter-chunk adjoint-state recurrence
     run chunks-backward with a VMEM-carried cotangent state
        G_exit(c-1) = exp(total_c) G_exit(c) + sum_i exp(cum_i) q_i (x) dy_i
     seeded with the final-state cotangent; emits G_exit per chunk (and the
     initial-state cotangent dh0 on the last reverse step).
  2. Transposed block-product kernel (chunk-parallel, no carried state):
     per chunk, with H_in and G_exit resident,
        dq = (dY V^T . dec) K + e^{cum} dY H_in^T
        dk = (dY V^T . dec)^T Q + w (V G_exit^T)
        dv = (Q K^T . dec)^T dY + w (K G_exit)
     plus the per-position decay-cotangent rows
        dcum_t = q_t . dq_t - k_t . dk_t  and  dli_t = k_t . dk_t
     (``dec``/``w`` are the forward's decay mask and chunk-exit weights).

The log-decay gradient follows from the telescoping identity
  dL/dcum_t = q_t . dq_t - k_t . dk_t  (+ <dH_f, H_f> at the last position),
so ``dla`` is one reverse cumsum over the full sequence outside the kernel.

Segment ``reset`` rows (the §3.5 state-carry boundary — the scan analogue
of ``row_task = -1`` gating) use EXACT masks, never a -1e9 log-decay
sentinel (a sentinel summed into the f32 in-chunk cumsum absorbs every
later decay — ulp at 1e9 is ~64 — so all post-reset pairs would decay by
exp(0) = 1).  The reset position's decay is excluded from the cumsum (its
gradient is zeroed by a ``where`` outside the vjp) and every state path is
gated on the within-chunk reset count: intra-chunk pairs must share it,
the inter-chunk/carry terms survive only when it is zero, and the
chunk-exit weights only for the final sub-segment.  In the backward the
same gates make pre-reset dq/dk/dv EXACTLY zero under a post-reset loss,
and ``dla`` becomes a segment-bounded reverse cumsum (reverse cumsum minus
its value at the next segment start).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_terms(la, li, r, chunk: int, masked: bool):
    """Per-chunk decay terms from (Q, 1) columns of log-decay, log-input and
    reset rows.  Prefix sums and the column -> row flips are masked
    reductions over a (Q, Q) tile (2D VPU work), so every vector the
    kernels touch is a (Q, 1) column or a (1, Q) row.

    Returns cum (col, row), gain (col, row), the chunk total (1, 1) and the
    within-chunk reset-count gates: pair [Q, Q], entry [Q, 1], exit [Q, 1]
    and carry (1, 1) — all 1.0 when the op runs without resets."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (ii >= jj).astype(jnp.float32)   # [i, j] = j <= i
    triu = (ii <= jj).astype(jnp.float32)  # [i, j] = i <= j
    eye = (ii == jj).astype(jnp.float32)

    def row(col):
        return (eye * col).sum(axis=0, keepdims=True)

    la_row = row(la)
    cum_col = (tri * la_row).sum(axis=1, keepdims=True)   # inclusive cumsum
    cum_row = (triu * la).sum(axis=0, keepdims=True)
    total = la.sum(axis=0, keepdims=True)                 # (1, 1)
    gain_col = jnp.exp(li)
    gain_row = row(gain_col)
    if not masked:
        return (cum_col, cum_row, gain_col, gain_row, total, tri,
                1.0, 1.0, 1.0, 1.0)
    seg_col = (tri * row(r)).sum(axis=1, keepdims=True)   # inclusive count
    seg_row = (triu * r).sum(axis=0, keepdims=True)
    n_res = r.sum(axis=0, keepdims=True)                  # (1, 1)
    pair = (seg_col == seg_row).astype(jnp.float32)
    entry = (seg_col == 0).astype(jnp.float32)            # H_in reaches these
    exit_ = (seg_col == n_res).astype(jnp.float32)        # these feed H_out
    carry = (n_res == 0).astype(jnp.float32)              # H_in survives
    return (cum_col, cum_row, gain_col, gain_row, total, tri,
            pair, entry, exit_, carry)


def _decay(cum_col, cum_row, gain_row, tri, pair):
    # dec[i, j] = exp(cum_i - cum_j) * gain_j for j <= i (within a segment)
    return jnp.exp((cum_col - cum_row) * tri) * tri * gain_row * pair


def _kernel(
    q_ref,   # [1, 1, Q, dk]
    k_ref,   # [1, 1, Q, dk]
    v_ref,   # [1, 1, Q, dv]
    la_ref,  # [1, 1, Q, 1]
    li_ref,  # [1, 1, Q, 1]
    r_ref,   # [1, Q, 1] f32 reset rows
    h0_ref,  # [1, 1, dk, dv] initial state
    y_ref,   # [1, 1, Q, dv]
    hout_ref,  # [1, 1, dk, dv] final state out
    *rest,   # (hin_ref? [1, 1, dk, dv], h_ref scratch [dk, dv] f32)
    n_chunks: int,
    chunk: int,
    save_states: bool,
    masked: bool,
):
    h_ref = rest[-1]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0]

    if save_states:
        # entry state of THIS chunk — the backward's inter-chunk residual
        rest[0][0, 0] = h_ref[...]

    q = q_ref[0, 0].astype(jnp.float32)  # [Q, dk]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)  # [Q, dv]
    (cum, cum_row, gain, gain_row, total, tri,
     pair, entry, exit_, carry) = _chunk_terms(
        la_ref[0, 0], li_ref[0, 0], r_ref[0], chunk, masked)

    dec = _decay(cum, cum_row, gain_row, tri, pair)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(s * dec, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    qd = q * (jnp.exp(cum) * entry)
    y_inter = jax.lax.dot_general(qd, h_ref[...], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    w = jnp.exp(total - cum) * gain * exit_  # [Q, 1]
    h_ref[...] = (jnp.exp(total) * carry) * h_ref[...] + jax.lax.dot_general(
        k * w, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _emit():
        hout_ref[0, 0] = h_ref[...]


def _bwd_state_kernel(
    q_ref,     # [1, 1, Q, dk]  (chunk n-1-j: reversed index maps)
    dy_ref,    # [1, 1, Q, dv]
    la_ref,    # [1, 1, Q, 1]
    r_ref,     # [1, Q, 1] f32
    dhf_ref,   # [1, 1, dk, dv] final-state cotangent
    gexit_ref,  # [1, 1, dk, dv] chunk-exit adjoint out
    dh0_ref,   # [1, 1, dk, dv] initial-state cotangent out
    g_ref,     # scratch [dk, dv] f32
    *,
    n_chunks: int,
    chunk: int,
    masked: bool,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = dhf_ref[0, 0]

    # adjoint at THIS chunk's exit — consumed by the block-product kernel
    gexit_ref[0, 0] = g_ref[...]

    la = la_ref[0, 0]
    cum, _, _, _, total, _, _, entry, _, carry = _chunk_terms(
        la, jnp.zeros_like(la), r_ref[0], chunk, masked)
    qd = q_ref[0, 0].astype(jnp.float32) * (jnp.exp(cum) * entry)
    dy = dy_ref[0, 0].astype(jnp.float32)
    # G_exit(c-1) = e^{total} G_exit(c) + Qd^T dY  (reverse decay-cumsum);
    # a reset inside the chunk cuts both paths back to the entry state
    g_ref[...] = (jnp.exp(total) * carry) * g_ref[...] + jax.lax.dot_general(
        qd, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == n_chunks - 1)
    def _emit():
        dh0_ref[0, 0] = g_ref[...]


def _bwd_chunk_kernel(
    q_ref,     # [1, 1, Q, dk]
    k_ref,     # [1, 1, Q, dk]
    v_ref,     # [1, 1, Q, dv]
    la_ref,    # [1, 1, Q, 1]
    li_ref,    # [1, 1, Q, 1]
    r_ref,     # [1, Q, 1] f32
    dy_ref,    # [1, 1, Q, dv]
    hin_ref,   # [1, 1, dk, dv] chunk ENTRY state (saved by the forward)
    gexit_ref,  # [1, 1, dk, dv] chunk EXIT adjoint (reverse-scan kernel)
    dq_ref,    # [1, 1, Q, dk]
    dk_ref,    # [1, 1, Q, dk]
    dv_ref,    # [1, 1, Q, dv]
    dcum_ref,  # [1, 1, Q, 1]  q.dq - k.dk rows (decay cotangent, pre-cumsum)
    dli_ref,   # [1, 1, Q, 1]  k.dk rows (input-gate cotangent)
    *,
    chunk: int,
    masked: bool,
):
    q = q_ref[0, 0].astype(jnp.float32)   # [Q, dk]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)   # [Q, dv]
    dy = dy_ref[0, 0].astype(jnp.float32)
    (cum, cum_row, gain, gain_row, total, tri,
     pair, entry, exit_, _) = _chunk_terms(
        la_ref[0, 0], li_ref[0, 0], r_ref[0], chunk, masked)

    dec = _decay(cum, cum_row, gain_row, tri, pair)
    w = jnp.exp(total - cum) * gain * exit_  # [Q, 1]
    hin = hin_ref[0, 0]    # [dk, dv]
    gex = gexit_ref[0, 0]  # [dk, dv]

    sdv = jax.lax.dot_general(dy, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # dy_i.v_j
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)    # q_i.k_j
    p = sdv * dec

    # dq_i = sum_{j<=i} dec[i,j] (dy_i.v_j) k_j + e^{cum_i} H_in dy_i
    dq = jax.lax.dot_general(p, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq += (jnp.exp(cum) * entry) * jax.lax.dot_general(
        dy, hin, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # dk_t = sum_{i>=t} dec[i,t] (dy_i.v_t) q_i + w_t G_exit v_t
    dk = jax.lax.dot_general(p, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dk += w * jax.lax.dot_general(
        v, gex, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # dv_t = sum_{i>=t} dec[i,t] (q_i.k_t) dy_i + w_t G_exit^T k_t
    dv = jax.lax.dot_general(s * dec, dy, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dv += w * jax.lax.dot_general(
        k, gex, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    kdk = (k * dk).sum(axis=1, keepdims=True)
    dcum_ref[0, 0] = (q * dq).sum(axis=1, keepdims=True) - kdk
    dli_ref[0, 0] = kdk


def _maps(H: int, n: int, *, reverse: bool = False):
    """Index maps over the (B*H, n) grid; ``reverse`` visits chunks
    last-to-first."""

    def c(j):
        return n - 1 - j if reverse else j

    def xmap(bh, j):  # [B, H, S, d] and [B, H, S, 1]
        return (bh // H, bh % H, c(j), 0)

    def rmap(bh, j):  # per-batch reset rows [B, S, 1]
        return (bh // H, c(j), 0)

    def smap(bh, j):
        return (bh // H, bh % H, 0, 0)

    def cmap(bh, j):  # per-chunk [dk, dv] tiles, [B*H, n, dk, dv] layout
        return (bh, c(j), 0, 0)

    return xmap, rmap, smap, cmap


def _col(x):
    return x[..., None]


def _fwd_call(q, k, v, la, li, r, h0, chunk, interpret, masked, save_states):
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    Q = chunk
    n = S // Q
    grid = (B * H, n)
    xmap, rmap, smap, cmap = _maps(H, n)

    out_specs = [
        pl.BlockSpec((1, 1, Q, dv), xmap),
        pl.BlockSpec((1, 1, dk, dv), smap),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(v.shape, q.dtype),
        jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
    ]
    if save_states:
        out_specs.append(pl.BlockSpec((1, 1, dk, dv), cmap))
        out_shape.append(jax.ShapeDtypeStruct((B * H, n, dk, dv), jnp.float32))

    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=n, chunk=Q,
                          save_states=save_states, masked=masked),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dv), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
            pl.BlockSpec((1, Q, 1), rmap),
            pl.BlockSpec((1, 1, dk, dv), smap),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, _col(la), _col(li), _col(r.astype(jnp.float32)), h0)


def _seg_rev_cumsum(dcum, r, masked):
    """dla_t = sum_{i>=t, same segment} dC_i over the last axis of
    ``dcum`` [B, H, S]: the plain reverse cumsum minus its value at the
    NEXT segment's start (gathered via the global segment index) — exactly
    bounded, no sentinel arithmetic."""
    rev = jnp.flip(jnp.cumsum(jnp.flip(dcum, axis=2), axis=2), axis=2)
    if not masked:
        return rev
    B, H, S = dcum.shape
    seg = jnp.cumsum(r, axis=1)[:, None, :]  # [B, 1, S] global segment index
    bidx = jnp.arange(B)[:, None, None]
    hidx = jnp.arange(H)[None, :, None]
    # rev at each segment's first (reset) position, scattered by segment id
    starts = jnp.zeros((B, H, S + 2), dcum.dtype).at[
        bidx, hidx, jnp.where(r[:, None, :] > 0, seg, S + 1)
    ].add(rev * (r[:, None, :] > 0).astype(dcum.dtype))
    return rev - starts[bidx, hidx, jnp.minimum(seg + 1, S + 1)]


def _bwd_call(q, k, v, la, li, r, hin, hfin, dy, dhf, chunk, interpret,
              masked):
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    Q = chunk
    n = S // Q
    grid = (B * H, n)
    xmap, rmap, smap, cmap = _maps(H, n)
    rxmap, rrmap, _, rcmap = _maps(H, n, reverse=True)
    la_c, li_c, r_c = _col(la), _col(li), _col(r.astype(jnp.float32))

    gexit, dh0 = pl.pallas_call(
        functools.partial(_bwd_state_kernel, n_chunks=n, chunk=Q,
                          masked=masked),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, dk), rxmap),
            pl.BlockSpec((1, 1, Q, dv), rxmap),
            pl.BlockSpec((1, 1, Q, 1), rxmap),
            pl.BlockSpec((1, Q, 1), rrmap),
            pl.BlockSpec((1, 1, dk, dv), smap),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dk, dv), rcmap),
            pl.BlockSpec((1, 1, dk, dv), smap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, n, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, dy, la_c, r_c, dhf)

    dq, dkk, dvv, dcum, dli = pl.pallas_call(
        functools.partial(_bwd_chunk_kernel, chunk=Q, masked=masked),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dv), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
            pl.BlockSpec((1, Q, 1), rmap),
            pl.BlockSpec((1, 1, Q, dv), xmap),
            pl.BlockSpec((1, 1, dk, dv), cmap),
            pl.BlockSpec((1, 1, dk, dv), cmap),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dk), xmap),
            pl.BlockSpec((1, 1, Q, dv), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
            pl.BlockSpec((1, 1, Q, 1), xmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, la_c, li_c, r_c, dy, hin, gexit)

    # dla_t = sum_{i>=t, same segment} (q_i.dq_i - k_i.dk_i); the final-state
    # term <dH_f, H_f> enters at the LAST position (so only the final
    # segment's positions see it) before the segment-bounded reverse cumsum.
    dcum = dcum[..., 0].at[:, :, -1].add(
        jnp.einsum("bhkv,bhkv->bh", dhf, hfin))
    dla = _seg_rev_cumsum(dcum, r, masked)
    d_r = np.zeros(r.shape, jax.dtypes.float0)
    return dq, dkk, dvv, dla, dli[..., 0], d_r, dh0


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _mamba_scan(q, k, v, la, li, r, h0, chunk, interpret, masked):
    y, h = _fwd_call(q, k, v, la, li, r, h0, chunk, interpret, masked,
                     save_states=False)
    return y, h


def _mamba_scan_fwd(q, k, v, la, li, r, h0, chunk, interpret, masked):
    y, h, hin = _fwd_call(q, k, v, la, li, r, h0, chunk, interpret, masked,
                          save_states=True)
    return (y, h), (q, k, v, la, li, r, hin, h)


def _mamba_scan_bwd(chunk, interpret, masked, res, cts):
    q, k, v, la, li, r, hin, hfin = res
    dy, dhf = cts
    return _bwd_call(q, k, v, la, li, r, hin, hfin, dy.astype(q.dtype),
                     dhf.astype(jnp.float32), chunk, interpret, masked)


_mamba_scan.defvjp(_mamba_scan_fwd, _mamba_scan_bwd)


def mamba_scan_pallas(
    q: jax.Array,         # [B, S, H, dk]
    k: jax.Array,
    v: jax.Array,         # [B, S, H, dv]
    log_decay: jax.Array,  # [B, S, H]
    log_input: jax.Array,
    *,
    chunk: int = 256,
    h0: Optional[jax.Array] = None,  # [B, H, dk, dv]
    reset: Optional[jax.Array] = None,  # [B, S] 1.0 = new segment starts
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    if h0 is None:
        h0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    la = log_decay.astype(jnp.float32)
    if reset is None:
        r = jnp.zeros((B, S), jnp.int32)
    else:
        # the reset position's own decay is excluded from the in-kernel
        # cumsum; this where also zeroes its log_decay gradient
        la = jnp.where(reset[:, :, None] > 0, 0.0, la)
        r = (reset > 0).astype(jnp.int32)
    # head-major: each chunk is a (Q, d) tile of one head
    y, h = _mamba_scan(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), la.transpose(0, 2, 1),
        log_input.astype(jnp.float32).transpose(0, 2, 1), r,
        h0.astype(jnp.float32), Q, interpret, reset is not None,
    )
    return y.transpose(0, 2, 1, 3), h
