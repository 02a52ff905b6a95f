"""Jit-ready wrappers around the compute hot-spot kernels.

Each op has three execution paths:
  * ``xla``     — pure-jnp formulation (gather-einsum / flash-scan) that XLA
                  compiles well and GSPMD shards; default on CPU and in the
                  512-device dry-run.
  * ``pallas``  — the compiled ``pl.pallas_call`` kernel (BlockSpec VMEM
                  tiling); the default whenever the backend is a TPU.
  * ``pallas_interpret`` — the same kernel body executed in interpret mode;
                  used by the CPU test suite to validate the kernel against
                  ``ref.py``.

Training support matrix (forward / backward under ``jax.grad``):

  op                 xla        pallas           pallas_interpret
  -----------------  ---------  ---------------  ----------------
  grouped_lora       fwd+bwd    fwd+bwd (vjp)    fwd+bwd (vjp)
  packed_attention   fwd+bwd    fwd+bwd (vjp)    fwd+bwd (vjp)
  mamba_scan         fwd+bwd    fwd+bwd (vjp)    fwd+bwd (vjp)
  decode_attention   fwd        fwd              fwd
  quant_matmul       fwd        fwd              fwd

``decode_attention`` is the serving hot loop (one query token against a
padded per-row KV cache window); it is never differentiated, so all three
tiers are forward-only.
``quant_matmul`` is the int8 frozen-backbone matmul (PR 9): the Pallas
tiers stream int8 weight blocks + a per-output-channel scale vector and
dequantize in-register (``kernels/quant_matmul.py``); the xla tier is the
dequantize-then-einsum formulation, bitwise identical to running the dense
BaseOp on an explicitly dequantized weight — which is what makes adapter
gradients under a quantized backbone EXACTLY equal to the dequantized
reference on that tier.  "fwd" here means the backbone weight side: the
backbone is frozen, but adapter cotangents still flow through the
activation input on every tier (a ``custom_vjp`` dx on the Pallas tiers).  The Pallas tiers run the flash-decode split-KV
kernel (``kernels/decode_attention.py``): stage 1 computes partial
softmax per contiguous KV split on a ``[B*Hkv, n_splits]`` grid, stage 2
combines with the online-softmax reduction.

``xla`` paths differentiate by ordinary autodiff of the jnp formulation.
Every Pallas path carries a ``jax.custom_vjp`` backward kernel (see the
kernel modules), so ``set_impl("pallas")`` / ``set_impl("pallas_interpret")``
train the WHOLE hot loop — grouped adapter GEMMs, packed flash attention,
and the chunked SSD/GLA scan — end-to-end under ``jax.value_and_grad``;
there is no xla-only family left.
``packed_attention`` additionally accepts learned PREFIX k/v rows
(soft-prompt PEFT): extra leading segment rows with wildcard segment ids on
the Pallas tiers, an online-softmax carry init on the XLA tier — both
differentiable, with per-row gating.
``mamba_scan``'s Pallas backward is two kernels (reverse decay-cumsum
adjoint-state scan + chunk-parallel transposed block products; per-chunk
entry states saved by the forward) — see ``kernels/mamba_scan.py``.
Segment ``reset`` rows (the §3.5 state-carry boundary, the scan analogue of
``row_task = -1`` gating) are implemented with exact segment masks on every
tier, so reset values match the segment-sliced oracle and resets block
gradient flow across segment boundaries.

The impl flag is thread-local and read at *trace* time: jitted steps bake in
whichever impl was active when they were first traced, so flip the impl
before building/compiling steps, not between calls of a compiled step.
"""
from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


class _Impl(threading.local):
    def __init__(self) -> None:
        self.name: Optional[str] = None  # resolved on first use, not import


_IMPL = _Impl()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def set_impl(name: str) -> None:
    assert name in ("xla", "pallas", "pallas_interpret"), name
    if name == "pallas_interpret" and _on_tpu():
        raise ValueError(
            "pallas_interpret is the CPU test tier; a TPU runs the compiled "
            "'pallas' tier")
    _IMPL.name = name


def get_impl() -> str:
    """The active tier: whatever ``set_impl`` chose on this thread, else
    ``"pallas"`` on a TPU backend and ``"xla"`` everywhere else."""
    if _IMPL.name is None:
        _IMPL.name = "pallas" if _on_tpu() else "xla"
    return _IMPL.name


# ---------------------------------------------------------------------------
# grouped LoRA (multi-task fused adapter GEMM — paper §3.4.3 grouped kernels)
# ---------------------------------------------------------------------------


def grouped_lora(
    x: jax.Array,        # [B, S, d_in]  (task constant per batch row)
    a: jax.Array,        # [T, d_in, r]
    b: jax.Array,        # [T, r, d_out]
    row_task: jax.Array, # [B] int32 (-1 => no adapter)
    scale: jax.Array,    # [T] f32
    *,
    block_m: int = 128,
) -> jax.Array:
    impl = get_impl()
    B, S, d_in = x.shape
    if impl == "xla":
        # Batch-row gather: adapters indexed per row (B small), never per
        # token — the [B*S, d_in, r] row-gather would dominate HBM.
        t = jnp.maximum(row_task, 0)
        gate = (row_task >= 0).astype(jnp.float32) * scale[t]  # [B]
        a_r = a[t]  # [B, d_in, r]
        b_r = b[t]  # [B, r, d_out]
        h = jnp.einsum("bsd,bdr->bsr", x, a_r, preferred_element_type=jnp.float32)
        y = jnp.einsum("bsr,bro->bso", h, b_r.astype(jnp.float32))
        return (y * gate[:, None, None]).astype(x.dtype)
    from repro.kernels.grouped_lora import grouped_lora_pallas
    from repro.kernels.tiling import SUBLANE, fit_block

    xf = x.reshape(B * S, d_in)
    rows = jnp.repeat(row_task, S)
    # Tasks own whole batch rows, so any block_m dividing S keeps row_task
    # block-constant (the kernel's contract) — never straddle batch rows.
    out = grouped_lora_pallas(
        xf, a, b, rows, scale, block_m=fit_block(S, block_m, SUBLANE),
        interpret=(impl == "pallas_interpret"),
    )
    return out.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# packed (segment-masked) flash attention — §3.5 alignment consumer
# ---------------------------------------------------------------------------


def packed_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    *,
    prefix_kv: Optional[tuple] = None,   # (pk, pv): [B, P, Hkv, dh] each
    prefix_keep: Optional[jax.Array] = None,  # [B, P] 1.0 = row owns prefix
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Segment-masked flash attention; optionally with learned per-task
    PREFIX k/v rows (soft-prompt PEFT, §3.2).  A prefix row is visible to
    every query of its batch row — across the row's packed segments,
    regardless of causal position — iff ``prefix_keep`` gates it on.  On the
    XLA tier the prefix folds into the online-softmax carry init; on the
    Pallas tiers it enters the kernel as extra leading k/v segment rows with
    wildcard segment ids."""
    impl = get_impl()
    if impl == "xla":
        from repro.models.attention import flash_attention_pairs

        pref = None
        if prefix_kv is not None:
            pk, pv = prefix_kv
            keep = prefix_keep if prefix_keep is not None else jnp.ones(
                pk.shape[:2], jnp.float32)
            pref = (pk, pv, keep)
        return flash_attention_pairs(
            q, k, v, block=block_q, causal=causal,
            segment_ids=segment_ids, positions=positions, kv_prefix=pref,
        )
    from repro.kernels.packed_attention import packed_attention_pallas

    interpret = impl == "pallas_interpret"
    if prefix_kv is None:
        return packed_attention_pallas(
            q, k, v, segment_ids=segment_ids, positions=positions,
            causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    import math

    B, S = q.shape[0], q.shape[1]
    pk, pv = prefix_kv
    P = pk.shape[1]
    keep = prefix_keep if prefix_keep is not None else jnp.ones(
        (B, P), jnp.float32)
    # Pad the prefix rows so S + P keeps S's k-tile: an unpadded P (e.g. 16
    # on S=2048) leaves S + P no lane-aligned divisor and collapses the
    # k-tile to the whole sequence.  Pad rows are gated off (kseg = -2
    # matches no query), so they are pure masked work.
    pad = (-P) % math.gcd(S, block_k)
    if pad:
        pk = jnp.pad(pk, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pv = jnp.pad(pv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        keep = jnp.pad(keep, ((0, 0), (0, pad)))
        P += pad
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if segment_ids is None:
        segment_ids = jnp.zeros((B, S), jnp.int32)
    # prefix rows: position -1 (always causally visible), segment -1 when the
    # row's task owns the prefix (wildcard: matches every query segment) and
    # -2 otherwise (matches none) — the kernel's extra-segment-row contract.
    k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
    v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    k_positions = jnp.concatenate(
        [jnp.full((B, P), -1, jnp.int32), positions.astype(jnp.int32)], axis=1)
    k_segment_ids = jnp.concatenate(
        [jnp.where(keep > 0, -1, -2).astype(jnp.int32),
         segment_ids.astype(jnp.int32)], axis=1)
    return packed_attention_pallas(
        q, k_full, v_full, segment_ids=segment_ids, positions=positions,
        causal=causal, k_segment_ids=k_segment_ids, k_positions=k_positions,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# split-KV decode attention — co-serving decode hot loop (forward only)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,            # [B, 1, H, dh]
    k_cache: jax.Array,      # [B, Smax, Hkv, dh]
    v_cache: jax.Array,      # [B, Smax, Hkv, dh]
    cache_len: jax.Array,    # [] or [B] int32 — exclusive window end per row
    cache_start: Optional[jax.Array] = None,  # [] or [B] int32 — window start
    *,
    split_k: int = 256,
) -> jax.Array:
    """One-token decode attention over a padded per-row KV cache window
    ``[cache_start, cache_len)``.  The reserved soft-prompt prefix region
    sits at the bottom of the cache: rows that own folded prefix k/v have
    their ``cache_start`` lowered into it, all other rows start above it —
    the same window mask covers both.  Empty windows yield zeros (the
    denominator is clamped, never divided through).  The Pallas tiers read
    each KV element once via the split-KV kernel."""
    impl = get_impl()
    if impl == "xla":
        return _ref.decode_attention_ref(q, k_cache, v_cache, cache_len, cache_start)
    from repro.kernels.decode_attention import decode_attention_pallas

    return decode_attention_pallas(
        q, k_cache, v_cache, cache_len, cache_start,
        split_k=split_k, interpret=(impl == "pallas_interpret"),
    )


# ---------------------------------------------------------------------------
# int8 backbone matmul (dequant fused into the kernel) — QLoRA tier, PR 9
# ---------------------------------------------------------------------------


def quant_matmul(
    x: jax.Array,      # [*batch, *contract] activations
    q: jax.Array,      # [*contract, *out] int8 weight blocks
    scale: jax.Array,  # per-output-channel scale, keepdims over *contract
    einsum_str: str,
) -> jax.Array:
    """The BaseOp einsum against an int8 frozen-backbone weight.

    ``einsum_str`` is the site's dense einsum (e.g. ``"bsd,dhk->bshk"``);
    every BaseOp site contracts x's trailing axes against q's leading axes,
    which is what lets the Pallas tiers flatten to one 2D
    ``y = (x @ q) * scale`` problem.  Gradients flow through ``x`` only.
    """
    impl = get_impl()
    if impl == "xla":
        # dequantize-then-einsum: the IDENTICAL graph to the dense BaseOp on
        # an explicitly dequantized weight (exact adapter-grad parity)
        return jnp.einsum(einsum_str, x, q.astype(jnp.float32) * scale)
    from repro.kernels.quant_matmul import quant_matmul_pallas

    lhs, out_sub = einsum_str.split("->")
    xs, ws = lhs.split(",")
    contract = [c for c in xs if c in ws]
    batch = [c for c in xs if c not in ws]
    wout = [c for c in ws if c not in xs]
    assert xs == "".join(batch + contract), einsum_str
    assert ws == "".join(contract + wout), einsum_str
    assert out_sub == "".join(batch + wout), einsum_str
    nb, nc = len(batch), len(contract)
    batch_shape, out_shape = x.shape[:nb], q.shape[nc:]
    M = 1
    for s in batch_shape:
        M *= s
    K = 1
    for s in x.shape[nb:]:
        K *= s
    N = 1
    for s in out_shape:
        N *= s
    y = quant_matmul_pallas(
        x.reshape(M, K), q.reshape(K, N), scale.reshape(N),
        interpret=(impl == "pallas_interpret"),
    )
    return y.reshape(*batch_shape, *out_shape)


# ---------------------------------------------------------------------------
# chunked SSD/GLA scan — zamba2/xlstm hot-spot
# ---------------------------------------------------------------------------


def mamba_scan(
    q: jax.Array,          # [B, S, H, dk]
    k: jax.Array,          # [B, S, H, dk]
    v: jax.Array,          # [B, S, H, dv]
    log_decay: jax.Array,  # [B, S, H]
    log_input: jax.Array,  # [B, S, H]
    *,
    chunk: int = 256,
    h0: Optional[jax.Array] = None,     # [B, H, dk, dv]
    reset: Optional[jax.Array] = None,  # [B, S] 1.0 = new segment starts here
):
    """Chunked SSD/GLA scan -> (y, final_state); fwd+bwd on every tier.

    ``reset`` erases the carried state exactly at packed-segment boundaries
    (§3.5 state-carry dependency).  Both impls implement it with exact
    segment masks (matching within-chunk reset counts) — never a -1e9
    log-decay sentinel, which the f32 cumsum would absorb — so values match
    the segment-sliced oracle and gradients cannot leak across boundaries
    under autodiff of either path."""
    impl = get_impl()
    if impl == "xla":
        from repro.models.ssm import chunked_gla

        return chunked_gla(q, k, v, log_decay, log_input, chunk, h0=h0,
                           reset=reset)
    from repro.kernels.mamba_scan import mamba_scan_pallas

    return mamba_scan_pallas(
        q, k, v, log_decay, log_input, chunk=chunk, h0=h0, reset=reset,
        interpret=(impl == "pallas_interpret"),
    )
