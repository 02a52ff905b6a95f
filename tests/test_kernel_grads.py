"""Gradient parity: Pallas custom_vjp kernels vs ref.py, on a selectable tier.

The §3.4.3 grouped kernels must be *trainable*: ``jax.grad`` through the
Pallas tier has to match autodiff of the pure-jnp oracles, including the
awkward cases — rows with ``row_task == -1`` (no adapter), multi-segment
packed attention rows, GQA head grouping, the per-task ``scale`` grad, and
the chunked SSD/GLA scan's state carry across chunk boundaries (entry-state
residuals + reverse adjoint recurrence).

CI runs this file as a matrix over ``REPRO_KERNEL_IMPL``:

  xla               — the jnp formulations' autodiff vs the oracles
  pallas_interpret  — the Pallas kernel bodies (interpret mode; default)
  pallas            — the compiled TPU kernels (dispatchable TPU leg)

The env var picks the ops-level tier under test AND whether the direct
kernel calls run interpreted, so the same file proves every cell of the
``kernels/ops.py`` support matrix on the hardware it has.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.grouped_lora import grouped_lora_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.packed_attention import (packed_attention_pallas,
                                            prefix_pad, tiles)
from repro.kernels.ref import (grouped_lora_ref, mamba_scan_ref,
                               packed_attention_ref)

# Tier under test (see module docstring): ops-level parity tests compare
# ``xla`` against KERNEL_TIER; direct kernel calls interpret unless the
# compiled-TPU leg is requested.
KERNEL_TIER = os.environ.get("REPRO_KERNEL_IMPL", "pallas_interpret")
assert KERNEL_TIER in ("xla", "pallas", "pallas_interpret"), KERNEL_TIER
INTERPRET = KERNEL_TIER != "pallas"

# Backbone storage precision for the end-to-end train-step leg.  The CI
# matrix runs the int8 leg against every tier — proving the quantized
# backbone (PR 9) trains through the same grouped-kernel routing as bf16.
BACKBONE_DTYPE = os.environ.get("REPRO_BACKBONE_DTYPE", "bfloat16")

# Direct kernel-body-vs-oracle tests exercise the Pallas kernels whatever
# the env says — running them again on the xla leg would only repeat the
# pallas_interpret leg's work, so that leg keeps the ops-level/e2e tests.
skip_on_xla = pytest.mark.skipif(
    KERNEL_TIER == "xla",
    reason="pallas kernel-body contract; identical on the pallas legs")

# Tier-vs-xla parity degenerates to x == x when the tier IS xla; the xla
# leg keeps the oracle-grounded tests (prefix rows, reset semantics,
# segmented-oracle, engine signature) instead.
skip_parity_on_xla = pytest.mark.skipif(
    KERNEL_TIER == "xla",
    reason="tier-vs-xla parity is tautological on the xla leg")


def _max_err(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))


class _impl:
    """Scoped kops impl flip (restores the previous tier on exit)."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.prev = kops.get_impl()
        kops.set_impl(self.name)

    def __exit__(self, *exc):
        kops.set_impl(self.prev)


# ---------------------------------------------------------------------------
# grouped LoRA
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,d_in,d_out,T,r,bm,bk",
    [
        (256, 256, 192, 3, 8, 64, 128),   # tasks + a -1 block, uneven dims
        (128, 512, 64, 2, 16, 128, 512),  # single M block per task
        (64, 128, 128, 1, 32, 64, 128),   # one task
    ],
)
@skip_on_xla
def test_grouped_lora_grads_match_ref(dtype, M, d_in, d_out, T, r, bm, bk, key):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, d_in), dtype)
    a = (jax.random.normal(ks[1], (T, d_in, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[2], (T, r, d_out)) * 0.05).astype(dtype)
    rt = np.full(M, -1, np.int32)
    for i in range(M // bm):
        rt[i * bm : (i + 1) * bm] = (i % (T + 1)) - 1  # includes -1 blocks
    rt = jnp.asarray(rt)
    scale = jnp.arange(1, T + 1, dtype=jnp.float32)
    g = jax.random.normal(ks[3], (M, d_out), dtype)

    def loss_pal(x, a, b, scale):
        y = grouped_lora_pallas(x, a, b, rt, scale, block_m=bm, block_k=bk,
                                interpret=INTERPRET)
        return (y.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    def loss_ref(x, a, b, scale):
        y = grouped_lora_ref(x, a, b, rt, scale)
        return (y.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    vp, gp = jax.value_and_grad(loss_pal, argnums=(0, 1, 2, 3))(x, a, b, scale)
    vr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2, 3))(x, a, b, scale)
    rtol, atol = (8e-2, 5e-1) if dtype == jnp.bfloat16 else (1e-4, 1e-3)
    np.testing.assert_allclose(float(vp), float(vr), rtol=rtol, atol=atol)
    for name, p, q in zip(("dx", "da", "db", "dscale"), gp, gr):
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(q, np.float32),
            rtol=rtol, atol=atol, err_msg=name,
        )


@skip_on_xla
def test_grouped_lora_no_adapter_rows_get_zero_grad(key):
    """Rows with row_task == -1 must contribute exactly zero to dx/da/db."""
    M, d_in, d_out, T, r, bm = 128, 128, 64, 2, 4, 64
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (M, d_in))
    a = jax.random.normal(ks[1], (T, d_in, r)) * 0.1
    b = jax.random.normal(ks[2], (T, r, d_out)) * 0.1
    rt = jnp.asarray([-1] * bm + [1] * bm, jnp.int32)
    scale = jnp.ones((T,))

    def loss(x, a, b):
        y = grouped_lora_pallas(x, a, b, rt, scale, block_m=bm,
                                interpret=INTERPRET)
        return (y ** 2).sum()

    dx, da, db = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    np.testing.assert_array_equal(np.asarray(dx[:bm]), 0.0)   # -1 rows
    np.testing.assert_array_equal(np.asarray(da[0]), 0.0)     # unused task slot
    np.testing.assert_array_equal(np.asarray(db[0]), 0.0)
    assert float(jnp.abs(da[1]).max()) > 0 and float(jnp.abs(db[1]).max()) > 0


@skip_parity_on_xla
def test_grouped_lora_ops_impl_parity_under_grad(key):
    """kops.grouped_lora: grads under set_impl(KERNEL_TIER) == xla."""
    B, S, d, dout, T, r = 6, 32, 48, 40, 3, 4
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, S, d))
    a = jax.random.normal(ks[1], (T, d, r)) * 0.1
    b = jax.random.normal(ks[2], (T, r, dout)) * 0.1
    rt = jnp.array([0, 1, -1, 2, 0, 1], jnp.int32)
    scale = jnp.array([1.5, 0.5, 2.0])
    g = jax.random.normal(ks[3], (B, S, dout))

    def loss(x, a, b):
        return (kops.grouped_lora(x, a, b, rt, scale) * g).sum()

    with _impl("xla"):
        vx, gx = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, a, b)
    with _impl(KERNEL_TIER):
        vp, gp = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, a, b)
    assert _max_err(vp, vx) < 1e-3
    for name, p, q in zip(("dx", "da", "db"), gp, gx):
        assert _max_err(p, q) < 1e-3, (name, _max_err(p, q))


# ---------------------------------------------------------------------------
# packed attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,dh,bq,bk,causal,packed",
    [
        (2, 128, 4, 2, 32, 64, 64, True, False),    # GQA causal
        (1, 256, 4, 4, 64, 128, 128, True, True),   # packed, 2 segments
        (2, 128, 8, 2, 16, 32, 64, False, False),   # non-causal, G=4
        (2, 128, 2, 1, 32, 128, 32, True, True),    # packed, asymmetric blocks
        (1, 512, 2, 1, 16, 256, None, True, True),  # kv tile follows q: 256
    ],
)
@skip_on_xla
def test_packed_attention_grads_match_ref(dtype, B, S, H, Hkv, dh, bq, bk,
                                          causal, packed, key):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    seg = pos = None
    if packed:
        half = S // 2
        seg = jnp.concatenate(
            [jnp.zeros((B, half), jnp.int32), jnp.ones((B, half), jnp.int32)],
            axis=1,
        )
        pos = jnp.broadcast_to(
            jnp.concatenate([jnp.arange(half), jnp.arange(half)]).astype(jnp.int32),
            (B, S),
        )
    g = jax.random.normal(ks[3], (B, S, H, dh), dtype)

    def loss_pal(q, k, v):
        o = packed_attention_pallas(q, k, v, seg, pos, causal, block_q=bq,
                                    block_k=bk, interpret=INTERPRET,
                                    scale=1 / np.sqrt(dh))
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    def loss_ref(q, k, v):
        o = packed_attention_ref(q, k, v, seg, pos, causal)
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    vp, gp = jax.value_and_grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    vr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    rtol, atol = (1e-1, 5e-1) if dtype == jnp.bfloat16 else (1e-3, 2e-3)
    np.testing.assert_allclose(float(vp), float(vr), rtol=rtol, atol=atol)
    for name, p, q_ in zip(("dq", "dk", "dv"), gp, gr):
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(q_, np.float32),
            rtol=rtol, atol=atol, err_msg=name,
        )


@skip_on_xla
def test_packed_attention_multisegment_grads(key):
    """4 ragged segments per row + padding tail (fully-masked final rows)."""
    B, S, H, dh = 1, 128, 2, 16
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))
    g = jax.random.normal(ks[3], (B, S, H, dh))
    lens = [48, 32, 24, 24]  # ragged chunk-packed row
    seg_np = np.concatenate([np.full(n, i, np.int32) for i, n in enumerate(lens)])
    pos_np = np.concatenate([np.arange(n, dtype=np.int32) for n in lens])
    seg = jnp.broadcast_to(jnp.asarray(seg_np), (B, S))
    pos = jnp.broadcast_to(jnp.asarray(pos_np), (B, S))

    def loss_pal(q, k, v):
        o = packed_attention_pallas(q, k, v, seg, pos, True, block_q=32,
                                    block_k=32, interpret=INTERPRET,
                                    scale=1 / np.sqrt(dh))
        return (o * g).sum()

    def loss_ref(q, k, v):
        return (packed_attention_ref(q, k, v, seg, pos, True) * g).sum()

    gp = jax.grad(loss_pal, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, p, q_ in zip(("dq", "dk", "dv"), gp, gr):
        assert _max_err(p, q_) < 2e-3, (name, _max_err(p, q_))


def test_packed_attention_prefix_rows_grads(key):
    """Learned prefix k/v rows (soft-prompt PEFT): the Pallas wildcard-
    segment path and the XLA carry-init path agree with a dense reference,
    gradients included, and ungated rows' prefixes get exactly zero grad."""
    _check_prefix_rows(key, B=2, S=64, H=4, Hkv=2, dh=16, P=8, block_q=128)


def test_packed_attention_prefix_rows_default_tiles(key):
    """The same at the model's 512-row q tile, where S + P = 1024 + 128
    (16 prefix rows padded to the lane) takes a non-square 512 x 384 tile."""
    assert tiles(1024, 1024 + 16 + prefix_pad(1024, 16), 512) == (512, 384)
    _check_prefix_rows(key, B=2, S=1024, H=2, Hkv=1, dh=16, P=16, block_q=512)


def _check_prefix_rows(key, *, B, S, H, Hkv, dh, P, block_q):
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    pk = jax.random.normal(ks[3], (B, P, Hkv, dh)) * 0.5
    pv = jax.random.normal(ks[4], (B, P, Hkv, dh)) * 0.5
    keep = jnp.asarray([[1.0] * P, [0.0] * P])  # row 0 gated on, row 1 off
    half = S // 2
    seg = jnp.concatenate([jnp.zeros((B, half), jnp.int32),
                           jnp.ones((B, half), jnp.int32)], axis=1)
    pos = jnp.broadcast_to(
        jnp.concatenate([jnp.arange(half), jnp.arange(half)]).astype(jnp.int32),
        (B, S))
    g = jax.random.normal(ks[5], (B, S, H, dh))

    def dense_ref(q, k, v, pk, pv):
        G = H // Hkv
        q5 = q.reshape(B, S, Hkv, G, dh).astype(jnp.float32)
        kf = jnp.concatenate([pk, k], 1).astype(jnp.float32)
        vf = jnp.concatenate([pv, v], 1).astype(jnp.float32)
        s = jnp.einsum("bskgd,bpkd->bskgp", q5, kf) / np.sqrt(dh)
        kseg = jnp.concatenate(
            [jnp.where(keep > 0, -1, -2).astype(jnp.int32), seg], 1)
        kpos = jnp.concatenate([jnp.full((B, P), -1, jnp.int32), pos], 1)
        mask = ((seg[:, :, None] == kseg[:, None, :])
                | (kseg[:, None, :] == -1))
        mask &= pos[:, :, None] >= kpos[:, None, :]
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bskgp,bpkd->bskgd", p, vf).reshape(B, S, H, dh)

    def loss_ref(q, k, v, pk, pv):
        return (dense_ref(q, k, v, pk, pv) * g).sum()

    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(q, k, v, pk, pv)
    for impl in ("xla", KERNEL_TIER):
        with _impl(impl):

            def loss(q, k, v, pk, pv):
                o = kops.packed_attention(q, k, v, segment_ids=seg,
                                          positions=pos, causal=True,
                                          prefix_kv=(pk, pv),
                                          prefix_keep=keep, block_q=block_q,
                                          scale=1 / np.sqrt(dh))
                return (o * g).sum()

            gp = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, pk, pv)
        for name, a, b in zip(("dq", "dk", "dv", "dpk", "dpv"), gp, gr):
            assert _max_err(a, b) < 1e-3, (impl, name, _max_err(a, b))
        np.testing.assert_array_equal(np.asarray(gp[3][1]), 0.0)
        np.testing.assert_array_equal(np.asarray(gp[4][1]), 0.0)


# ---------------------------------------------------------------------------
# mamba_scan (chunked SSD/GLA): reverse decay-cumsum + transposed products
# ---------------------------------------------------------------------------


def _gla_inputs(key, B, S, H, dk, dv, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    q = jax.random.normal(ks[0], (B, S, H, dk), dtype)
    k = jax.random.normal(ks[1], (B, S, H, dk), dtype) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, dv), dtype)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    li = jnp.log(jax.nn.softplus(jax.random.normal(ks[4], (B, S, H))) + 1e-3)
    g = jax.random.normal(ks[5], (B, S, H, dv)).astype(jnp.float32)
    gh = jax.random.normal(ks[6], (B, H, dk, dv)) * 0.3
    return q, k, v, la, li, g, gh


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,dk,dv,Q",
    [
        (2, 128, 2, 16, 32, 32),  # 4 chunks: state straddles 3 boundaries
        (1, 256, 4, 64, 64, 64),  # wider heads, 4 chunks
        (2, 64, 1, 8, 8, 64),     # single chunk (Q == S): no carry at all
    ],
)
@skip_on_xla
def test_mamba_scan_grads_match_ref(dtype, B, S, H, dk, dv, Q, key):
    """Both outputs get cotangents: y AND the final state (the dla identity's
    <dH_f, H_f> term and the reverse-scan seed are exercised)."""
    q, k, v, la, li, g, gh = _gla_inputs(key, B, S, H, dk, dv, dtype)

    def loss_pal(q, k, v, la, li):
        y, h = mamba_scan_pallas(q, k, v, la, li, chunk=Q, interpret=INTERPRET)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    def loss_ref(q, k, v, la, li):
        y, h = mamba_scan_ref(q, k, v, la, li)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    vp, gp = jax.value_and_grad(loss_pal, argnums=(0, 1, 2, 3, 4))(q, k, v, la, li)
    vr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2, 3, 4))(q, k, v, la, li)
    rtol, atol = (8e-2, 5e-1) if dtype == jnp.bfloat16 else (1e-4, 1e-3)
    np.testing.assert_allclose(float(vp), float(vr), rtol=rtol, atol=atol)
    for name, p, r in zip(("dq", "dk", "dv", "dla", "dli"), gp, gr):
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(r, np.float32),
            rtol=rtol, atol=atol, err_msg=name,
        )


@skip_on_xla
def test_mamba_scan_h0_grads_match_ref(key):
    """Initial-state input: dh0 comes off the reverse scan's last step."""
    B, S, H, dk, dv, Q = 1, 96, 3, 8, 8, 32
    q, k, v, la, li, g, gh = _gla_inputs(key, B, S, H, dk, dv)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (B, H, dk, dv)) * 0.5

    def loss_pal(q, k, v, la, li, h0):
        y, h = mamba_scan_pallas(q, k, v, la, li, chunk=Q, h0=h0,
                                 interpret=INTERPRET)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    def loss_ref(q, k, v, la, li, h0):
        y, h = mamba_scan_ref(q, k, v, la, li, h0=h0)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    gp = jax.grad(loss_pal, argnums=tuple(range(6)))(q, k, v, la, li, h0)
    gr = jax.grad(loss_ref, argnums=tuple(range(6)))(q, k, v, la, li, h0)
    for name, p, r in zip(("dq", "dk", "dv", "dla", "dli", "dh0"), gp, gr):
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(r, np.float32),
            rtol=1e-4, atol=1e-3, err_msg=name,
        )


@skip_parity_on_xla
def test_mamba_scan_ops_impl_parity_under_grad(key):
    """kops.mamba_scan: grads under set_impl(KERNEL_TIER) == xla, including
    a chunk-straddling segment reset (position 24 inside a 16-chunk)."""
    B, S, H, dk, dv, Q = 2, 64, 2, 8, 8, 16
    q, k, v, la, li, g, gh = _gla_inputs(key, B, S, H, dk, dv)
    reset = jnp.zeros((B, S)).at[:, 24].set(1.0)

    def loss(q, k, v, la, li):
        y, h = kops.mamba_scan(q, k, v, la, li, chunk=Q, reset=reset)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    with _impl("xla"):
        vx, gx = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, la, li)
    with _impl(KERNEL_TIER):
        vp, gp = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, la, li)
    assert _max_err(vp, vx) < 1e-3
    for name, p, x_ in zip(("dq", "dk", "dv", "dla", "dli"), gp, gx):
        assert _max_err(p, x_) < 1e-3, (name, _max_err(p, x_))


def test_mamba_scan_reset_blocks_cross_segment_grads(key):
    """A reset boundary is the scan's row gate (the ``row_task = -1``
    analogue): loss on the post-reset segment must put EXACTLY zero gradient
    on every pre-reset input — no state-carry leak through the backward.
    The exactness matters: the segment masks gate each term to 0.0 rather
    than summing a -1e9 sentinel the f32 cumsum would absorb."""
    B, S, H, dk, dv, Q = 1, 64, 2, 8, 8, 16
    q, k, v, la, li, g, _ = _gla_inputs(key, B, S, H, dk, dv)
    r = 24  # straddles a chunk: the boundary masks run inside chunk 1
    reset = jnp.zeros((B, S)).at[:, r].set(1.0)

    def loss(q, k, v, la, li):
        y, _ = kops.mamba_scan(q, k, v, la, li, chunk=Q, reset=reset)
        return (y.astype(jnp.float32)[:, r:] * g[:, r:]).sum()

    with _impl(KERNEL_TIER):
        grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, la, li)
    for name, t in zip(("dq", "dk", "dv", "dli"), (*grads[:3], grads[4])):
        np.testing.assert_array_equal(np.asarray(t[:, :r]), 0.0, err_msg=name)
    dla = np.asarray(grads[3][:, :r])
    if KERNEL_TIER == "xla":
        # chunked_gla's autodiffed cumsum transpose leaves +-cancellation
        # dust on the decay cotangent; the custom_vjp identity is exact
        assert float(np.abs(dla).max()) < 1e-5
    else:
        np.testing.assert_array_equal(dla, 0.0, err_msg="dla")
    assert float(jnp.abs(grads[1][:, r:]).max()) > 0  # post-reset grads flow


def test_mamba_scan_reset_matches_segmented_oracle(key):
    """Reset semantics are grounded in the sequential oracle, not in
    tier-vs-tier parity (which a shared bug would satisfy): a packed row
    with resets must equal the oracle run per segment with fresh state —
    values, final state, and every gradient."""
    B, S, H, dk, dv, Q = 1, 64, 2, 8, 8, 16
    q, k, v, la, li, g, gh = _gla_inputs(key, B, S, H, dk, dv)
    cuts = [5, 24, 40]  # mid-chunk, straddling, plus a short first segment
    reset = jnp.zeros((B, S)).at[:, jnp.asarray(cuts)].set(1.0)
    bounds = [0] + cuts + [S]

    def loss_oracle(q, k, v, la, li):
        tot = 0.0
        for a, b in zip(bounds[:-1], bounds[1:]):
            # fresh state per segment; the reset position's decay is unused
            # (zero state) so the slice needs no masking of its own
            y, h = mamba_scan_ref(q[:, a:b], k[:, a:b], v[:, a:b],
                                  la[:, a:b], li[:, a:b])
            tot += (y.astype(jnp.float32) * g[:, a:b]).sum()
            if b == S:
                tot += (h * gh).sum()
        return tot

    def loss(q, k, v, la, li):
        y, h = kops.mamba_scan(q, k, v, la, li, chunk=Q, reset=reset)
        return (y.astype(jnp.float32) * g).sum() + (h * gh).sum()

    vr, gr = jax.value_and_grad(loss_oracle, argnums=(0, 1, 2, 3, 4))(
        q, k, v, la, li)
    with _impl(KERNEL_TIER):
        vp, gp = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            q, k, v, la, li)
    np.testing.assert_allclose(float(vp), float(vr), rtol=1e-4, atol=1e-4)
    for name, p, r_ in zip(("dq", "dk", "dv", "dla", "dli"), gp, gr):
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(r_, np.float32),
            rtol=1e-4, atol=1e-4, err_msg=name,
        )


def _seg_rev_sum_loop(dcum, reset):
    """float64 loop: dla_t = sum of dcum_i over i >= t in t's segment."""
    dcum = np.asarray(dcum, np.float64)
    out = np.zeros_like(dcum)
    for b in range(dcum.shape[0]):
        acc = np.zeros(dcum.shape[1])
        for t in reversed(range(dcum.shape[2])):
            acc = acc + dcum[b, :, t]
            out[b, :, t] = acc
            if reset[b, t]:
                acc = np.zeros_like(acc)
    return out


_RESET_PATTERNS = {
    "start": lambda Q, S: [0],              # the training cells' own case
    "mid_chunk": lambda Q, S: [Q + 5],
    "chunk_boundary": lambda Q, S: [Q],
    "consecutive": lambda Q, S: [Q + 3, Q + 4],
    "last": lambda Q, S: [S - 1],
    "several_in_chunk": lambda Q, S: [Q + 1, Q + 4, Q + 9, Q + 10],
    "quiet_chunk_between": lambda Q, S: [3, 2 * Q + 5],
    "none": lambda Q, S: [],
}


@pytest.mark.parametrize("Q", [16, 32])
@pytest.mark.parametrize("pattern", list(_RESET_PATTERNS))
def test_mamba_scan_segmented_reverse_sum_matches_loop(pattern, Q):
    """The scan backward's chunked, segment-bounded reverse sum (``dla``)
    against a float64 loop, on reset patterns that cut chunks anywhere;
    the second row has its own pattern.  A segment ending at a reset sees
    nothing after it: with ``dcum`` zero before a row's last reset, ``dla``
    is EXACTLY zero there."""
    from repro.kernels.mamba_scan import _seg_rev_cumsum

    B, H, S = 2, 3, 96
    reset = np.zeros((B, S), np.int32)
    reset[0, _RESET_PATTERNS[pattern](Q, S)] = 1
    reset[1, [0, S // 2 + 1]] = 1
    dcum = np.random.default_rng(Q).standard_normal((B, H, S)).astype(
        np.float32)
    dla = np.asarray(_seg_rev_cumsum(jnp.asarray(dcum), jnp.asarray(reset),
                                     Q, True))
    np.testing.assert_allclose(dla, _seg_rev_sum_loop(dcum, reset),
                               rtol=1e-5, atol=1e-5)

    for b in range(B):
        last = int(np.flatnonzero(reset[b]).max(initial=0))
        dcum[b, :, :last] = 0.0
    dla = np.asarray(_seg_rev_cumsum(jnp.asarray(dcum), jnp.asarray(reset),
                                     Q, True))
    for b in range(B):
        last = int(np.flatnonzero(reset[b]).max(initial=0))
        np.testing.assert_array_equal(dla[b, :, :last], 0.0)
        assert np.abs(dla[b, :, last:]).max() > 0


# ---------------------------------------------------------------------------
# ssm / hybrid cells: the scan backward inside the real model blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ["mamba2", "mlstm"])
@skip_parity_on_xla
def test_ssm_cell_grads_tier_vs_xla(cell, key):
    """A full zamba2/xlstm cell (conv, gates, norms, base-op projections
    around the scan) trains on the Pallas tier: value_and_grad parity with
    the xla path at f32 tightness (acceptance: rtol <= 1e-4)."""
    from repro.configs import smoke_config
    from repro.models import ssm
    from repro.models.layers import materialize

    if cell == "mamba2":
        cfg = smoke_config("zamba2-2.7b")
        spec, apply = ssm.mamba2_spec(cfg), ssm.mamba2_apply
    else:
        cfg = smoke_config("xlstm-1.3b")
        spec, apply = ssm.mlstm_spec(cfg), ssm.mlstm_apply
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          materialize(spec, key))
    B, S = 2, 32  # ssm_chunk=16 -> two chunks: inter-chunk carry exercised
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (B, S, cfg.d_model), jnp.float32)
    g = jax.random.normal(ks[1], (B, S, cfg.d_model), jnp.float32)

    def loss(params, x):
        y, _ = apply(params, x, cfg)
        return (y.astype(jnp.float32) * g).sum()

    with _impl("xla"):
        vx, gx = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    with _impl(KERNEL_TIER):
        vp, gp = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    np.testing.assert_allclose(float(vp), float(vx), rtol=1e-4, atol=1e-4)
    flat_x, _ = jax.tree_util.tree_flatten_with_path(gx)
    flat_p = jax.tree.leaves(gp)
    assert len(flat_x) == len(flat_p) > 0
    for (path, tx), tp in zip(flat_x, flat_p):
        np.testing.assert_allclose(
            np.asarray(tp, np.float32), np.asarray(tx, np.float32),
            rtol=1e-4, atol=1e-4, err_msg=jax.tree_util.keystr(path),
        )


# ---------------------------------------------------------------------------
# int8 quant matmul (PR 9): fwd parity + the custom_vjp dx path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N", [(128, 256, 192), (64, 96, 64)])
@skip_on_xla
def test_quant_matmul_kernel_grads_match_ref(M, K, N, key):
    from repro.kernels.quant_matmul import (quant_matmul_pallas,
                                            quant_matmul_ref)
    from repro.models.quantize import quantize_weight

    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    w = jax.random.normal(ks[1], (K, N), jnp.float32) * 0.1
    qw = quantize_weight(w, (-2,))
    q, scale = qw["q"], qw["scale"].reshape(N)
    g = jax.random.normal(ks[2], (M, N), jnp.float32)

    def run_k(x):
        return (quant_matmul_pallas(x, q, scale, interpret=INTERPRET) * g).sum()

    def run_r(x):
        return (quant_matmul_ref(x, q, scale) * g).sum()

    yk = quant_matmul_pallas(x, q, scale, interpret=INTERPRET)
    yr = quant_matmul_ref(x, q, scale)
    assert _max_err(yk, yr) < 1e-4
    vk, dk = jax.value_and_grad(run_k)(x)
    vr, dr = jax.value_and_grad(run_r)(x)
    np.testing.assert_allclose(float(vk), float(vr), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               rtol=1e-4, atol=1e-3)


@skip_parity_on_xla
def test_quant_matmul_op_tier_vs_xla(key):
    """The 3D einsum dispatcher (flatten -> kernel -> reshape) matches the
    xla tier's dequantized einsum, value and dx."""
    from repro.models.quantize import quantize_weight

    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (2, 16, 32), jnp.float32)
    w = jax.random.normal(ks[1], (32, 4, 8), jnp.float32) * 0.1
    qw = quantize_weight(w, (-3,))
    g = jax.random.normal(ks[2], (2, 16, 4, 8), jnp.float32)

    def loss(x):
        y = kops.quant_matmul(x, qw["q"], qw["scale"], "bsd,dhk->bshk")
        return (y * g).sum()

    with _impl("xla"):
        vx, dx = jax.value_and_grad(loss)(x)
    with _impl(KERNEL_TIER):
        vp, dp = jax.value_and_grad(loss)(x)
    np.testing.assert_allclose(float(vp), float(vx), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dx),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# end-to-end: value_and_grad of a full train step under the Pallas tier
# ---------------------------------------------------------------------------


def _train_step_grads(cfg_name, targets, key, seq_len=32):
    from repro.configs import smoke_config
    from repro.models.transformer import build_model
    from repro.peft.adapters import LORA
    from repro.peft.methods import AdapterConfig
    from repro.peft.multitask import MultiTaskAdapters, TaskSegments

    cfg = smoke_config(cfg_name)
    if BACKBONE_DTYPE != cfg.backbone_dtype:
        cfg = cfg.with_overrides(backbone_dtype=BACKBONE_DTYPE)
    m = build_model(cfg)
    params = m.init(key)
    if cfg.backbone_dtype == "int8":
        from repro.models.quantize import quantize_backbone

        params = quantize_backbone(params, cfg)
    mta = MultiTaskAdapters(cfg, [AdapterConfig(LORA, rank=4, targets=targets),
                                  AdapterConfig(LORA, rank=4, targets=targets)])
    seg = TaskSegments.contiguous([2, 2])
    ad = mta.init(jax.random.PRNGKey(1))
    ctxf = mta.ctx_factory(seg)
    B, S = 4, seq_len
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                                     cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }

    def loss_fn(ad):
        out = m.forward(params, batch, adapters=ad, ctx_factory=ctxf)
        return seg.per_task_loss(out["per_token_loss"], batch["loss_mask"]).sum()

    with _impl("xla"):
        lx, gx = jax.value_and_grad(loss_fn, allow_int=True)(ad)
    with _impl(KERNEL_TIER):
        lp, gp = jax.value_and_grad(loss_fn, allow_int=True)(ad)
    return lx, gx, lp, gp


@pytest.mark.parametrize(
    "cfg_name,targets",
    [
        ("llama3.2-3b", ("attn_q", "attn_k", "attn_v", "attn_o")),
        # adapters on the ssm projections: adapter grads flow THROUGH the
        # scan backward (grouped-LoRA vjp composed with mamba_scan vjp)
        ("zamba2-2.7b", ("ssm_in", "ssm_out", "attn_q", "attn_v")),
        # xlstm: ssm_out is declared at the mLSTM inner width, which the
        # sLSTM block (w_out at d_model) can't consume — use the sites every
        # xlstm cell agrees on
        ("xlstm-1.3b", ("ssm_in", "attn_q", "attn_v")),
    ],
)
@skip_parity_on_xla
def test_train_step_grads_tier_vs_xla(cfg_name, targets, key):
    """A full multi-task train-step backward on the Pallas tier must match
    the XLA tier for every backbone family the kernels serve — dense
    (grouped-LoRA + packed-attention) and hybrid/ssm (mamba_scan): the
    §3.4.3 kernels actually train, with no xla-only family left."""
    lx, gx, lp, gp = _train_step_grads(cfg_name, targets, key)
    assert np.isfinite(float(lp))
    np.testing.assert_allclose(float(lp), float(lx), rtol=2e-3, atol=2e-3)
    flat_x = jax.tree.leaves(gx)
    flat_p = jax.tree.leaves(gp)
    assert len(flat_x) == len(flat_p) and len(flat_x) > 0
    for tx, tp in zip(flat_x, flat_p):
        np.testing.assert_allclose(np.asarray(tp, np.float32),
                                   np.asarray(tx, np.float32),
                                   rtol=5e-2, atol=5e-3)


def test_engine_step_signature_is_impl_sensitive():
    """Compiled hTask steps bake in the trace-time kernel impl, so the
    engine's step cache must key on it — flipping set_impl between plans
    has to miss, not reuse a step compiled for the other tier."""
    from repro.configs import smoke_config
    from repro.core import (ExecutionPlanner, ModelGenerator, ParallelismSpec,
                            PEFTEngine)
    from repro.data import make_task
    from repro.peft.adapters import LORA
    from repro.peft.methods import AdapterConfig

    cfg = smoke_config("llama3.2-3b")
    tasks = [make_task("t0", "sst2", 2, AdapterConfig(LORA, rank=4), seed=0)]
    planner = ExecutionPlanner(cfg, ParallelismSpec(num_stages=2,
                                                    chips_per_stage=1))
    plan = planner.plan(tasks, n_micro=1)
    gen = ModelGenerator(cfg)
    gen.register_tasks(tasks)
    eng = PEFTEngine(gen, plan, lr=1e-3)
    with _impl("xla"):
        sig_x = eng.step_signature(0)
    with _impl("pallas_interpret"):
        sig_p = eng.step_signature(0)
    assert sig_x != sig_p
    with _impl("xla"):
        assert eng.step_signature(0) == sig_x  # stable within a tier
