"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_lora import grouped_lora_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.packed_attention import (packed_attention_pallas, prefix_pad,
                                            tiles)
from repro.kernels import ops as kops
from repro.kernels.ref import grouped_lora_ref, mamba_scan_ref, packed_attention_ref
from repro.models.ssm import chunked_gla


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,d_in,d_out,T,r,bm,bk",
    [
        (256, 256, 192, 3, 8, 64, 128),
        (128, 512, 64, 2, 16, 128, 512),
        (512, 384, 384, 5, 4, 64, 128),
        (64, 128, 128, 1, 32, 64, 128),
    ],
)
def test_grouped_lora_kernel(dtype, M, d_in, d_out, T, r, bm, bk, key):
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (M, d_in), dtype)
    a = (jax.random.normal(ks[1], (T, d_in, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[2], (T, r, d_out)) * 0.05).astype(dtype)
    rt = np.full(M, -1, np.int32)
    for i in range(M // bm):
        rt[i * bm : (i + 1) * bm] = (i % (T + 1)) - 1
    rt = jnp.asarray(rt)
    scale = jnp.arange(1, T + 1, dtype=jnp.float32)
    ref = grouped_lora_ref(x, a, b, rt, scale)
    out = grouped_lora_pallas(x, a, b, rt, scale, block_m=bm, block_k=bk, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_grouped_lora_xla_path_matches_ref(key):
    B, S, d, dout, T, r = 6, 32, 48, 40, 3, 4
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (B, S, d), jnp.float32)
    a = jax.random.normal(ks[1], (T, d, r)) * 0.1
    b = jax.random.normal(ks[2], (T, r, dout)) * 0.1
    rt = jnp.array([0, 1, -1, 2, 0, 1], jnp.int32)
    scale = jnp.array([1.5, 0.5, 2.0])
    y = kops.grouped_lora(x, a, b, rt, scale)
    ref = grouped_lora_ref(x.reshape(-1, d), a, b, jnp.repeat(rt, S), scale)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, dout), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,dh,bq,bk,causal,packed",
    [
        (2, 128, 4, 2, 32, 64, 64, True, False),
        (1, 256, 4, 4, 64, 128, 128, True, True),
        (2, 128, 8, 2, 16, 32, 64, False, False),
        (2, 128, 2, 1, 32, 128, 32, True, True),
        (1, 64, 1, 1, 8, 64, 64, True, False),
        (1, 512, 2, 1, 16, 256, None, True, True),  # kv tile follows q: 256
    ],
)
def test_packed_attention_kernel(dtype, B, S, H, Hkv, dh, bq, bk, causal, packed, key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    seg = pos = None
    if packed:
        half = S // 2
        seg = jnp.concatenate(
            [jnp.zeros((B, half), jnp.int32), jnp.ones((B, half), jnp.int32)], axis=1
        )
        pos = jnp.broadcast_to(
            jnp.concatenate([jnp.arange(half), jnp.arange(half)]).astype(jnp.int32), (B, S)
        )
    if bk is None:
        assert tiles(S, S, bq) == (bq, bq) and bq != 128
    ref = packed_attention_ref(q, k, v, seg, pos, causal)
    out = packed_attention_pallas(q, k, v, seg, pos, causal, block_q=bq, block_k=bk,
                                  interpret=True, scale=1 / np.sqrt(dh))
    tol = 4e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("S", [64, 200, 256, 1024, 4096])
@pytest.mark.parametrize("block_q", [128, 512])
@pytest.mark.parametrize("P", [0, 16, 128])
def test_packed_attention_tile_rule(S, block_q, P):
    """The kv tile divides Sk, is a lane multiple or all of Sk, is the q
    tile on an S x S call, and prefix rows pad to the lane, not the tile."""
    pad = prefix_pad(S, P) if P else 0
    Sk = S + P + pad
    bq, bk = tiles(S, Sk, block_q)
    assert S % bq == 0 and bq <= max(block_q, S)
    assert Sk % bk == 0 and (bk % 128 == 0 or bk == Sk)
    if S % 128 == 0:
        assert bq == min(block_q, S)
        assert (P + pad) % 128 == 0 and pad < 128
        if Sk == S:
            assert bk == bq
        else:
            assert bk % 128 == 0 and bk <= bq
    # an explicit kv tile is still fitted to Sk
    assert tiles(S, Sk, block_q, 128)[1] == (128 if Sk % 128 == 0 else Sk)


def test_flash_pairs_matches_dense_ref(key):
    """The model's jnp flash (exact-causal) is equivalent to dense attention."""
    from repro.models.attention import flash_attention_kvscan, flash_attention_pairs

    B, S, H, Hkv, dh = 2, 128, 4, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    ref = packed_attention_ref(q, k, v, None, None, True)
    out1 = flash_attention_pairs(q, k, v, block=32, causal=True, scale=1 / np.sqrt(dh))
    out2 = flash_attention_kvscan(q, k, v, kv_block=32, causal=True, scale=1 / np.sqrt(dh))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "B,S,H,dk,dv,Q",
    [(2, 128, 2, 16, 32, 32), (1, 256, 4, 64, 64, 64), (2, 64, 1, 8, 8, 64)],
)
def test_mamba_scan_kernel(B, S, H, dk, dv, Q, key):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, S, H, dk), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, dk), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    li = jnp.log(jax.nn.softplus(jax.random.normal(ks[4], (B, S, H))) + 1e-3)
    y_ref, h_ref = mamba_scan_ref(q, k, v, la, li)
    y, h = mamba_scan_pallas(q, k, v, la, li, chunk=Q, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=2e-4, atol=2e-4)
    # the model's chunked formulation agrees with the sequential oracle too
    y2, h2 = chunked_gla(q, k, v, la, li, Q)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_ref), rtol=2e-4, atol=2e-4)


def test_mamba_scan_kernel_h0(key):
    """Initial state enters the kernel's chunk-0 scratch init (was an
    assert before the backward landed)."""
    B, S, H, dk, dv, Q = 2, 64, 2, 8, 16, 32
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, S, H, dk), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, dk), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    li = jnp.log(jax.nn.softplus(jax.random.normal(ks[4], (B, S, H))) + 1e-3)
    h0 = jax.random.normal(ks[5], (B, H, dk, dv)) * 0.5
    y_ref, h_ref = mamba_scan_ref(q, k, v, la, li, h0=h0)
    y, h = mamba_scan_pallas(q, k, v, la, li, chunk=Q, h0=h0, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=2e-4, atol=2e-4)


def test_gla_reset_isolates_segments(key):
    """reset=1 at a position must erase all prior state (packed SSM rows)."""
    B, S, H, dk, dv = 1, 64, 2, 8, 8
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk)) * 0.3
    v = jax.random.normal(ks[2], (B, S, H, dv))
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    li = jnp.zeros((B, S, H))
    reset = jnp.zeros((B, S)).at[:, 32].set(1.0)
    y, _ = chunked_gla(q, k, v, la, li, 16, reset=reset)
    y2, _ = chunked_gla(q[:, 32:], k[:, 32:], v[:, 32:],
                        la[:, 32:], li[:, 32:], 16,
                        reset=jnp.zeros((B, 32)).at[:, 0].set(1.0))
    np.testing.assert_allclose(np.asarray(y[:, 32:]), np.asarray(y2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# split-KV decode attention (PR 6): single-token query against a KV cache
# window [cache_start, cache_len) — the co-serving decode hot loop.
# ---------------------------------------------------------------------------


def _decode_oracle_np(q, k_cache, v_cache, cache_len, cache_start):
    """Brute-force per-(row, head) numpy oracle, independent of the jnp ref."""
    q = np.asarray(q, np.float32)
    kc = np.asarray(k_cache, np.float32)
    vc = np.asarray(v_cache, np.float32)
    B, _one, H, dh = q.shape
    S, Hkv = kc.shape[1], kc.shape[2]
    G = H // Hkv
    out = np.zeros((B, 1, H, dh), np.float32)
    for b in range(B):
        lo, hi = int(cache_start[b]), int(cache_len[b])
        if hi <= lo:
            continue
        for h in range(H):
            kv = h // G
            s = (kc[b, lo:hi, kv] @ q[b, 0, h]) / np.sqrt(dh)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, 0, h] = p @ vc[b, lo:hi, kv]
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,dh,S,split",
    [
        (3, 4, 2, 16, 64, 16),     # GQA, several splits
        (2, 8, 8, 32, 128, 128),   # MHA, single split covering the cache
        (1, 2, 1, 8, 48, 48),      # single row, one split
        (2, 6, 3, 16, 96, 7),      # split not dividing S (largest-divisor fit)
    ],
)
def test_decode_attention_kernel(dtype, B, H, Hkv, dh, S, split, key):
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.ref import decode_attention_ref

    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    # per-row windows, including a reserved-prefix row (cache_start > 0)
    cache_len = jnp.asarray([(S // 2 + 3 * i) % S + 1 for i in range(B)], jnp.int32)
    cache_start = jnp.asarray([0] + [2] * (B - 1), jnp.int32)
    ref = decode_attention_ref(q, kc, vc, cache_len, cache_start)
    out = decode_attention_pallas(q, kc, vc, cache_len, cache_start,
                                  split_k=split, interpret=True)
    tol = 4e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)
    if dtype == jnp.float32:
        oracle = _decode_oracle_np(q, kc, vc, cache_len, cache_start)
        np.testing.assert_allclose(np.asarray(out, np.float32), oracle,
                                   rtol=1e-4, atol=1e-4)


def test_decode_attention_tiers_match(key):
    """kops.decode_attention parity: xla tier vs pallas_interpret tier."""
    B, H, Hkv, dh, S = 2, 4, 2, 16, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    cache_len = jnp.asarray([40, 17], jnp.int32)
    cache_start = jnp.asarray([0, 4], jnp.int32)
    prev = kops.get_impl()
    try:
        kops.set_impl("xla")
        y_xla = kops.decode_attention(q, kc, vc, cache_len, cache_start)
        kops.set_impl("pallas_interpret")
        y_pal = kops.decode_attention(q, kc, vc, cache_len, cache_start,
                                      split_k=16)
    finally:
        kops.set_impl(prev)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_xla),
                               rtol=1e-4, atol=1e-4)


def test_decode_attention_single_split_matches_multi(key):
    from repro.kernels.decode_attention import decode_attention_pallas

    B, H, Hkv, dh, S = 2, 4, 2, 16, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    cache_len = jnp.asarray([50, 33], jnp.int32)
    one = decode_attention_pallas(q, kc, vc, cache_len, split_k=S, interpret=True)
    many = decode_attention_pallas(q, kc, vc, cache_len, split_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(many), np.asarray(one),
                               rtol=1e-5, atol=1e-5)


def test_decode_attention_empty_window_finite_zeros(key):
    """Regression: an empty [start, len) window (freshly-bound or inactive
    pool row) must yield exact finite zeros, not NaN from a 0/0 softmax."""
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.ref import decode_attention_ref

    B, H, Hkv, dh, S = 3, 4, 2, 16, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    cache_len = jnp.asarray([0, 8, 8], jnp.int32)
    cache_start = jnp.asarray([0, 8, 2], jnp.int32)  # rows 0 and 1 are empty
    for out in (decode_attention_ref(q, kc, vc, cache_len, cache_start),
                decode_attention_pallas(q, kc, vc, cache_len, cache_start,
                                        split_k=8, interpret=True)):
        arr = np.asarray(out)
        assert np.all(np.isfinite(arr))
        np.testing.assert_array_equal(arr[:2], np.zeros_like(arr[:2]))
        assert np.any(arr[2] != 0)
