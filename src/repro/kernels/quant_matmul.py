"""Int8 backbone matmul with in-register dequantization (TPU Pallas).

The quantized-backbone tier (QLoRA-style, PR 9) stores every adapter-capable
backbone weight as ``{"q": int8, "scale": f32}`` with a symmetric
per-output-channel scale.  The hot-path matmul must NOT materialize the
dequantized weight in HBM — that would forfeit the 2x byte win that lets
more tenants co-reside.  Instead this kernel streams int8 weight tiles into
VMEM, casts to f32 *in register*, accumulates x @ q in an f32 VMEM scratch
over k-tiles of each (block_m, block_n) output tile, and applies the
per-column scale once at the final emit:

    y[M, N] = (x[M, K] @ q[K, N].astype(f32)) * scale[N]

Scaling after the k-accumulation is exact for symmetric per-output-channel
quantization (the scale is constant along the contracted axis), so the only
difference vs dequantize-then-matmul is f32 summation order.

The backbone is frozen — gradients never flow to ``q``/``scale`` — but
adapter gradients DO flow through ``x`` (an adapter at layer i receives its
cotangent through every deeper backbone op).  The wrapper therefore carries
a ``custom_vjp`` whose backward is the dequantize-then-matmul cotangent
  dx = (g * scale) @ q^T
computed as a plain jnp contraction (training-path only; the serving hot
loop never differentiates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, SUBLANE, fit_block


def _qmm_kernel(
    x_ref,      # [block_m, block_k]
    q_ref,      # [block_k, block_n] int8
    s_ref,      # [1, block_n] f32
    o_ref,      # [block_m, block_n]
    acc_ref,    # [block_m, block_n] f32 scratch
    *,
    n_k: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 -> f32 happens on the VMEM tile (in register), never in HBM
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), q_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _qmm_call(x, q, scale, *, block_m: int, block_n: int, block_k: int,
              interpret: bool):
    M, K = x.shape
    N = q.shape[1]
    n_m, n_n, n_k = M // block_m, N // block_n, K // block_k
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(x, q, scale.reshape(1, N))


def quant_matmul_pallas(
    x: jax.Array,      # [M, K]
    q: jax.Array,      # [K, N] int8
    scale: jax.Array,  # [N] f32
    *,
    block_m: int = 128,
    block_n: int = 1024,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y = (x @ dequant(q, scale)) with the dequant fused into the kernel.

    Tiles are (block_m, block_k) x (block_k, block_n): N is tiled too, so the
    weight tile and the f32 accumulator stay a few MiB at any d_ff.
    Differentiable w.r.t. ``x`` only (the backbone is frozen); the backward
    contracts the cotangent against the int8 blocks directly.
    """
    M, K = x.shape
    K2, N = q.shape
    assert K == K2, (x.shape, q.shape)
    assert scale.shape == (N,), (scale.shape, N)
    block_m = fit_block(M, block_m, SUBLANE)
    block_n = fit_block(N, block_n, LANE)
    block_k = fit_block(K, block_k, LANE)

    @jax.custom_vjp
    def qmm(x):
        return _qmm_call(x, q, scale, block_m=block_m, block_n=block_n,
                         block_k=block_k, interpret=interpret)

    def fwd(x):
        return qmm(x), None

    def bwd(_res, g):
        gs = g.astype(jnp.float32) * scale  # fold the column scale into dy
        dx = jax.lax.dot_general(
            gs, q.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (dx.astype(x.dtype),)

    qmm.defvjp(fwd, bwd)
    return qmm(x)


def quant_matmul_ref(x: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """Dequantize-then-matmul oracle (2D problem)."""
    w = q.astype(jnp.float32) * scale
    return jnp.einsum("mk,kn->mn", x.astype(jnp.float32), w).astype(x.dtype)
