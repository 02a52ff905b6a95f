"""Grouped multi-task LoRA kernel (TPU Pallas) — paper §4 "Grouped Kernels".

The GPU version assigns CUTLASS thread blocks to task adapters in proportion
to their FLOPs.  TPU adaptation: the fused batch is tiled into M-blocks of
``block_m`` rows; a *scalar-prefetched* per-block task table lets the
BlockSpec index maps stream exactly the owning task's A/B factors into VMEM
— the SGMV pattern re-thought for the MXU.  Because LoRA rank (<=64) is far
below the 128 MXU lane width, per-task GEMMs would idle the systolic array
(the paper's §2.2 underutilization); grouping all tasks into one kernel
amortizes that — the weight streams change per block while the pipeline
stays busy.

Contract (checked in the wrapper): ``row_task`` is constant within each
``block_m`` row block.  The §3.5 chunk alignment guarantees this: fused rows
are chunk-aligned (chunk >= 64) and tasks own whole rows.

Two matmuls are fused: h = x @ A[t] accumulates over d_in tiles in a VMEM
scratch; on the last k-tile, y = h @ B[t] * scale[t] writes the output tile.

The op is differentiable via ``jax.custom_vjp``: the forward under autodiff
additionally spills the rank-space activations h = x @ A[t] ([M, r] f32 —
tiny next to x), so the backward kernel skips recomputing the first GEMM.
The backward streams the same scalar-prefetched block-task table and fuses
all three gradient GEMMs per block:

  dh    = (g @ B[t]^T) * scale[t]          (rank-space cotangent, scratch)
  dX    = dh @ A[t]^T                      (per-block tile, written once)
  dA_p  = x^T @ dh                         (per-BLOCK partial, [n_m,d_in,r])
  dM_p  = h^T @ g                          (per-BLOCK partial, [n_m,r,d_out])

Per-task accumulation (dA[t] = sum of its blocks' partials) happens as one
XLA scatter-add outside the kernel — every Pallas output block is written
exactly once, so no output-revisiting hazards on the TPU pipeline.  dB and
dscale derive from the unscaled dM partials: dB[t] = scale[t] * M[t] and
dscale[t] = <B[t], M[t]>.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, fit_block


def _fwd_kernel(
    # scalar prefetch
    block_task_ref,  # [n_m] int32
    scale_ref,       # [T] f32
    # inputs
    x_ref,           # [1, block_m, block_k]
    a_ref,           # [1, block_k, r]
    b_ref,           # [1, r, d_out]
    # outputs
    o_ref,           # [1, block_m, d_out]
    *rest,           # (h_out_ref?, h_ref scratch)
    n_k: int,
    save_h: bool,
):
    h_ref = rest[-1]
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h_ref[...] += jax.lax.dot_general(
        x_ref[0], a_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _emit():
        t = block_task_ref[i]
        gate = jnp.where(t >= 0, scale_ref[jnp.maximum(t, 0)], 0.0)
        y = jax.lax.dot_general(
            h_ref[...], b_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0] = (y * gate).astype(o_ref.dtype)
        if save_h:
            rest[0][0] = h_ref[...]


def _bwd_kernel(
    # scalar prefetch
    block_task_ref,  # [n_m] int32
    scale_ref,       # [T] f32
    # inputs
    x_ref,           # [1, block_m, block_k]
    g_ref,           # [1, block_m, d_out]   (dy)
    h_ref,           # [1, block_m, r] f32   (saved rank activations)
    a_ref,           # [1, block_k, r]
    b_ref,           # [1, r, d_out]
    # outputs
    dx_ref,          # [1, block_m, block_k]
    dap_ref,         # [1, block_k, r]    per-block dA partial
    dmp_ref,         # [1, r, d_out]      per-block unscaled dB partial
    # scratch
    dh_ref,          # [block_m, r] f32
    *,
    n_k: int,
):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _head():
        t = block_task_ref[i]
        valid = jnp.where(t >= 0, 1.0, 0.0)
        gate = valid * scale_ref[jnp.maximum(t, 0)]
        g = g_ref[0].astype(jnp.float32)
        # dh = (g @ B^T) * scale — gated to zero for adapter-less blocks
        dh_ref[...] = jax.lax.dot_general(
            g, b_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * gate
        # unscaled dB partial: h^T @ g (valid-gated; scale applied outside)
        dmp_ref[0] = jax.lax.dot_general(
            h_ref[0], g,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * valid

    # dX tile: dh @ A^T over this d_in tile
    dx_ref[0] = jax.lax.dot_general(
        dh_ref[...], a_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dx_ref.dtype)
    # per-block dA partial for this d_in tile: x^T @ dh
    dap_ref[0] = jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), dh_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# Row blocks ride a leading [n_m] axis, [n_m, block_m, d]: a block's last
# two dims are then (block_m, d-tile) with block_m the whole array dim, legal
# on the TPU at any block_m — decode's one-row blocks included.
def _row_tile(i, k, bt, sc):
    return (i, 0, k)


def _row_block(i, k, bt, sc):
    return (i, 0, 0)


def _fwd_call(x, a, b, row_task, scale, block_m, block_k, interpret, save_h):
    M, d_in = x.shape
    T, _, r = a.shape
    d_out = b.shape[-1]
    n_m, n_k = M // block_m, d_in // block_k

    block_task = row_task[:: block_m].astype(jnp.int32)  # [n_m] (block-constant)

    out_shape = [jax.ShapeDtypeStruct((n_m, block_m, d_out), x.dtype)]
    out_specs = [pl.BlockSpec((1, block_m, d_out), _row_block)]
    if save_h:
        out_shape.append(jax.ShapeDtypeStruct((n_m, block_m, r), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block_m, r), _row_block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_m, n_k),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), _row_tile),
            pl.BlockSpec(
                (1, block_k, r), lambda i, k, bt, sc: (jnp.maximum(bt[i], 0), k, 0)
            ),
            pl.BlockSpec(
                (1, r, d_out), lambda i, k, bt, sc: (jnp.maximum(bt[i], 0), 0, 0)
            ),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_m, r), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=n_k, save_h=save_h),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )
    out = fn(block_task, scale.astype(jnp.float32),
             x.reshape(n_m, block_m, d_in), a, b)
    y = out[0].reshape(M, d_out)
    return (y, out[1]) if save_h else y


def _bwd_call(x, a, b, row_task, scale, h, g, block_m, block_k, interpret):
    M, d_in = x.shape
    T, _, r = a.shape
    d_out = b.shape[-1]
    n_m, n_k = M // block_m, d_in // block_k
    block_task = row_task[:: block_m].astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_m, n_k),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), _row_tile),
            pl.BlockSpec((1, block_m, d_out), _row_block),
            pl.BlockSpec((1, block_m, r), _row_block),
            pl.BlockSpec(
                (1, block_k, r), lambda i, k, bt, sc: (jnp.maximum(bt[i], 0), k, 0)
            ),
            pl.BlockSpec(
                (1, r, d_out), lambda i, k, bt, sc: (jnp.maximum(bt[i], 0), 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, block_k), _row_tile),
            pl.BlockSpec((1, block_k, r), lambda i, k, bt, sc: (i, k, 0)),
            pl.BlockSpec((1, r, d_out), lambda i, k, bt, sc: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, r), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_bwd_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_m, block_m, d_in), x.dtype),
            jax.ShapeDtypeStruct((n_m, d_in, r), jnp.float32),
            jax.ShapeDtypeStruct((n_m, r, d_out), jnp.float32),
        ],
        interpret=interpret,
    )
    dx, da_p, dm_p = fn(block_task, scale.astype(jnp.float32),
                        x.reshape(n_m, block_m, d_in),
                        g.reshape(n_m, block_m, d_out), h, a, b)
    dx = dx.reshape(M, d_in)

    # Per-task reduction of the per-block partials (one scatter-add each).
    slots = jnp.maximum(block_task, 0)
    da = jnp.zeros((T, d_in, r), jnp.float32).at[slots].add(da_p)
    m = jnp.zeros((T, r, d_out), jnp.float32).at[slots].add(dm_p)
    db = m * scale.astype(jnp.float32)[:, None, None]
    dscale = jnp.einsum("tro,tro->t", m, b.astype(jnp.float32))
    return dx, da.astype(a.dtype), db.astype(b.dtype), dscale.astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_lora(x, a, b, row_task, scale, block_m, block_k, interpret):
    return _fwd_call(x, a, b, row_task, scale, block_m, block_k, interpret,
                     save_h=False)


def _grouped_lora_fwd(x, a, b, row_task, scale, block_m, block_k, interpret):
    y, h = _fwd_call(x, a, b, row_task, scale, block_m, block_k, interpret,
                     save_h=True)
    return y, (x, a, b, row_task, scale, h)


def _grouped_lora_bwd(block_m, block_k, interpret, res, g):
    x, a, b, row_task, scale, h = res
    dx, da, db, dscale = _bwd_call(
        x, a, b, row_task, scale, h, g, block_m, block_k, interpret
    )
    d_row_task = np.zeros(row_task.shape, jax.dtypes.float0)
    return dx, da, db, d_row_task, dscale


_grouped_lora.defvjp(_grouped_lora_fwd, _grouped_lora_bwd)


def grouped_lora_pallas(
    x: jax.Array,         # [M, d_in]
    a: jax.Array,         # [T, d_in, r]
    b: jax.Array,         # [T, r, d_out]
    row_task: jax.Array,  # [M] int32 (block-constant)
    scale: jax.Array,     # [T] f32
    *,
    block_m: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, d_in = x.shape
    # block_m is the caller's (it keeps row_task block-constant); d_in tiles
    # are lane blocks of x and dx, so a multiple of 128 or the whole d_in
    block_m = math.gcd(M, block_m)
    block_k = fit_block(d_in, block_k, LANE)
    return _grouped_lora(
        x, a, b, row_task.astype(jnp.int32), scale, block_m, block_k, interpret
    )
