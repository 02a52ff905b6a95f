"""Flash-decode-style split-KV decode attention (forward only).

One query token per row attends over a padded per-row KV cache window
``[cache_start, cache_len)``.  The dense path scores the whole ``Smax``
cache per token; here stage 1 partitions the cache into ``n_splits``
contiguous splits and computes a *partial* softmax per split — partial
output, running max and partial denominator — in parallel across a
``[B*Hkv, n_splits]`` grid.  Stage 2 reduces the partials with the
online-softmax combine in plain XLA (the reduction is tiny:
``[B, Hkv, n_splits, G]``).

The kernel reads a head-major ``[B, Hkv, Smax, dh]`` view of the cache, so
each split is a ``(split, dh)`` tile (the TPU's (8, 128) block rule); the
per-row window bounds ride scalar prefetch.  The wrapper transposes the
model's ``[B, Smax, Hkv, dh]`` cache into that view on every call.

Every KV element is read exactly once per decoded token, and splits that
fall entirely outside a row's window contribute ``(m=-1e30, l=0)`` which
vanish in the combine, so masked prefix padding costs bandwidth but never
flops downstream.  The reserved prefix region (soft-prompt rows below
``cache_start``) is handled by the same window mask.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import SUBLANE, fit_block

NEG_INF = -1e30


def _stage1_kernel(
    len_ref,     # [B] int32 scalar prefetch: window end per row
    start_ref,   # [B] int32 scalar prefetch: window start per row
    q_ref,       # [1, 1, G, dh]
    k_ref,       # [1, 1, split, dh]
    v_ref,       # [1, 1, split, dh]
    o_ref,       # [1, 1, 1, G, dh] f32 partial out
    m_ref,       # [1, 1, 1, G, 1]  f32 running max
    l_ref,       # [1, 1, 1, G, 1]  f32 partial denominator
    *,
    hkv: int,
    split: int,
    g: int,
    scale: float,
):
    b = pl.program_id(0) // hkv
    s_idx = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32)            # [G, dh]
    k = k_ref[0, 0].astype(jnp.float32)            # [split, dh]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # [G, split]
    pos = s_idx * split + jax.lax.broadcasted_iota(jnp.int32, (g, split), 1)
    mask = (pos >= start_ref[b]) & (pos < len_ref[b])
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)              # [G, 1]
    # re-mask after exp: a fully-masked split has m == NEG_INF and would
    # otherwise produce exp(0) == 1 on every masked column
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # [G, dh]
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = p.sum(axis=-1, keepdims=True)


def decode_attention_pallas(
    q: jax.Array,            # [B, 1, H, dh]
    k_cache: jax.Array,      # [B, Smax, Hkv, dh]
    v_cache: jax.Array,      # [B, Smax, Hkv, dh]
    cache_len: jax.Array,    # [] or [B] int32
    cache_start: Optional[jax.Array] = None,  # [] or [B] int32
    *,
    split_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, dh = q.shape
    Smax, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(dh)

    split = fit_block(Smax, split_k, SUBLANE)
    n_splits = Smax // split

    q4 = q.reshape(B, Hkv, G, dh)
    # head-major cache: each split is a (split, dh) tile of one kv head
    k4 = k_cache.transpose(0, 2, 1, 3)
    v4 = v_cache.transpose(0, 2, 1, 3)
    len_b = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    if cache_start is None:
        start_b = jnp.zeros((B,), jnp.int32)
    else:
        start_b = jnp.broadcast_to(jnp.asarray(cache_start, jnp.int32).reshape(-1), (B,))

    def head(bh, s, *_):
        return (bh // Hkv, bh % Hkv, 0, 0)

    def kv(bh, s, *_):
        return (bh // Hkv, bh % Hkv, s, 0)

    def part(bh, s, *_):
        return (bh // Hkv, bh % Hkv, s, 0, 0)

    kernel = functools.partial(_stage1_kernel, hkv=Hkv, split=split, g=G,
                               scale=scale)
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, n_splits),
            in_specs=[
                pl.BlockSpec((1, 1, G, dh), head),
                pl.BlockSpec((1, 1, split, dh), kv),
                pl.BlockSpec((1, 1, split, dh), kv),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, G, dh), part),
                pl.BlockSpec((1, 1, 1, G, 1), part),
                pl.BlockSpec((1, 1, 1, G, 1), part),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(len_b, start_b, q4, k4, v4)

    # stage 2: online-softmax combine across splits (tiny reduction)
    m_star = m_part.max(axis=2, keepdims=True)           # [B, Hkv, 1, G, 1]
    alpha = jnp.exp(m_part - m_star)                     # [B, Hkv, n, G, 1]
    l_star = (l_part * alpha).sum(axis=2)                # [B, Hkv, G, 1]
    out = (o_part * alpha).sum(axis=2)                   # [B, Hkv, G, dh]
    out = out / jnp.maximum(l_star, 1e-20)
    return out.reshape(B, 1, H, dh).astype(q.dtype)
