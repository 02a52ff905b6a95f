"""Kernel microbenchmarks: grouped LoRA vs per-task loop (the paper's
grouped-kernel claim), forward AND backward, across execution impls.

Rows:
  kernels/grouped_lora/{fwd,fwd_bwd}/<impl>/T_<n>
  kernels/packed_attention/{fwd,fwd_bwd}/<impl>/S_<n>
  kernels/mamba_scan/{fwd,fwd_bwd}/<impl>/S_<n>
  kernels/decode_attention/fwd/<impl>/S_<n>   (fwd-only: serving path)

``xla`` always runs.  ``pallas`` runs only on a real TPU backend.
``pallas_interpret`` is a correctness tier, not a perf tier — it runs one
small shape so the artifact tracks that the differentiable kernel path
stays alive, without minutes of interpreter time.

These rows are the BLOCKING slice of the cross-PR ``--compare`` regression
gate (see ``benchmarks/run.py --blocking kernels``): a kernel-microbench
regression beyond threshold fails CI, while serve/co-serve rows stay
advisory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, timeit
from repro.kernels import ops as kops


def _impls() -> list[str]:
    impls = ["xla"]
    if jax.default_backend() == "tpu":
        impls.append("pallas")
    return impls


def _bench_grouped_lora(rows: list[str]) -> None:
    key = jax.random.PRNGKey(0)
    B, S, d, dout, r = 8, 256, 512, 512, 16
    for T in (2, 4, 8):
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (B, S, d), jnp.float32)
        a = jax.random.normal(ks[1], (T, d, r)) * 0.05
        b = jax.random.normal(ks[2], (T, r, dout)) * 0.05
        rt = jnp.asarray([i % T for i in range(B)], jnp.int32)
        scale = jnp.ones((T,))
        g = jax.random.normal(ks[3], (B, S, dout), jnp.float32)

        @jax.jit
        def per_task(x):
            # ungrouped baseline: one masked GEMM pair per task (what a
            # naive multi-adapter loop does)
            out = jnp.zeros((B, S, dout), jnp.float32)
            for t in range(T):
                m = (rt == t).astype(jnp.float32)[:, None, None]
                h = jnp.einsum("bsd,dr->bsr", x * m, a[t])
                out += jnp.einsum("bsr,ro->bso", h, b[t])
            return out

        per_task(x).block_until_ready()
        tp = timeit(lambda: per_task(x).block_until_ready(), iters=5)

        for impl in _impls():
            kops.set_impl(impl)
            try:
                fwd = jax.jit(lambda x: kops.grouped_lora(x, a, b, rt, scale))

                def loss(x, a, b):
                    return (kops.grouped_lora(x, a, b, rt, scale) * g).sum()

                bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                fwd(x).block_until_ready()
                jax.block_until_ready(bwd(x, a, b))
                tf = timeit(lambda: fwd(x).block_until_ready(), iters=5)
                tb = timeit(lambda: jax.block_until_ready(bwd(x, a, b)), iters=5)
            finally:
                kops.set_impl("xla")
            rows.append(csv_row(
                f"kernels/grouped_lora/fwd/{impl}/T_{T}", tf * 1e6,
                f"per_task_us={tp*1e6:.1f};grouped_speedup=x{tp/tf:.2f}",
            ))
            rows.append(csv_row(
                f"kernels/grouped_lora/fwd_bwd/{impl}/T_{T}", tb * 1e6,
                f"fwd_us={tf*1e6:.1f};bwd_over_fwd=x{tb/tf:.2f}",
            ))


def _bench_packed_attention(rows: list[str]) -> None:
    key = jax.random.PRNGKey(1)
    B, H, Hkv, dh = 4, 8, 4, 64
    for S in (512, 1024):
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
        g = jax.random.normal(ks[3], (B, S, H, dh), jnp.float32)
        half = S // 2
        seg = jnp.concatenate(
            [jnp.zeros((B, half), jnp.int32), jnp.ones((B, half), jnp.int32)],
            axis=1,
        )
        pos = jnp.broadcast_to(
            jnp.concatenate([jnp.arange(half), jnp.arange(half)]).astype(jnp.int32),
            (B, S),
        )

        for impl in _impls():
            kops.set_impl(impl)
            try:
                fwd = jax.jit(lambda q, k, v: kops.packed_attention(
                    q, k, v, segment_ids=seg, positions=pos, causal=True,
                    scale=dh ** -0.5))

                def loss(q, k, v):
                    return (kops.packed_attention(
                        q, k, v, segment_ids=seg, positions=pos, causal=True,
                        scale=dh ** -0.5) * g).sum()

                bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                fwd(q, k, v).block_until_ready()
                jax.block_until_ready(bwd(q, k, v))
                tf = timeit(lambda: fwd(q, k, v).block_until_ready(), iters=5)
                tb = timeit(lambda: jax.block_until_ready(bwd(q, k, v)), iters=5)
            finally:
                kops.set_impl("xla")
            rows.append(csv_row(
                f"kernels/packed_attention/fwd/{impl}/S_{S}", tf * 1e6, "",
            ))
            rows.append(csv_row(
                f"kernels/packed_attention/fwd_bwd/{impl}/S_{S}", tb * 1e6,
                f"fwd_us={tf*1e6:.1f};bwd_over_fwd=x{tb/tf:.2f}",
            ))


def _bench_mamba_scan(rows: list[str]) -> None:
    key = jax.random.PRNGKey(3)
    B, H, dk, dv, chunk = 2, 4, 64, 64, 256
    for S in (512, 1024):
        ks = jax.random.split(key, 6)
        q = jax.random.normal(ks[0], (B, S, H, dk), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, dk), jnp.float32) * 0.3
        v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
        la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
        li = jnp.log(jax.nn.softplus(jax.random.normal(ks[4], (B, S, H))) + 1e-3)
        g = jax.random.normal(ks[5], (B, S, H, dv), jnp.float32)

        for impl in _impls():
            kops.set_impl(impl)
            try:
                fwd = jax.jit(lambda q, k, v, la, li: kops.mamba_scan(
                    q, k, v, la, li, chunk=chunk)[0])

                def loss(q, k, v, la, li):
                    y, _ = kops.mamba_scan(q, k, v, la, li, chunk=chunk)
                    return (y * g).sum()

                bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
                fwd(q, k, v, la, li).block_until_ready()
                jax.block_until_ready(bwd(q, k, v, la, li))
                tf = timeit(lambda: fwd(q, k, v, la, li).block_until_ready(),
                            iters=5)
                tb = timeit(lambda: jax.block_until_ready(bwd(q, k, v, la, li)),
                            iters=5)
            finally:
                kops.set_impl("xla")
            rows.append(csv_row(
                f"kernels/mamba_scan/fwd/{impl}/S_{S}", tf * 1e6, "",
            ))
            rows.append(csv_row(
                f"kernels/mamba_scan/fwd_bwd/{impl}/S_{S}", tb * 1e6,
                f"fwd_us={tf*1e6:.1f};bwd_over_fwd=x{tb/tf:.2f}",
            ))


def _bench_decode_attention(rows: list[str]) -> None:
    """Split-KV decode attention: one-token query against a short and a long
    KV-cache context (the co-serving decode hot loop is memory-bound in the
    cache sweep, so the long-context row is the one that matters)."""
    key = jax.random.PRNGKey(4)
    B, H, Hkv, dh = 8, 8, 4, 64
    for S in (256, 2048):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, 1, H, dh), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
        cache_len = jnp.full((B,), S - 1, jnp.int32)
        for impl in _impls():
            kops.set_impl(impl)
            try:
                fwd = jax.jit(lambda q, kc, vc: kops.decode_attention(
                    q, kc, vc, cache_len))
                fwd(q, kc, vc).block_until_ready()
                tf = timeit(lambda: fwd(q, kc, vc).block_until_ready(),
                            iters=10)
            finally:
                kops.set_impl("xla")
            rows.append(csv_row(
                f"kernels/decode_attention/fwd/{impl}/S_{S}", tf * 1e6,
                f"B={B};ctx={S - 1}",
            ))


def _bench_quant_matmul(rows: list[str]) -> None:
    """Int8 backbone matmul (PR 9): x[M,K] @ int8 q[K,N] with dequant fused
    in-register.  Decode-regime (small M) and train-regime (large M) rows;
    fwd-only — the backbone is frozen, adapter cotangents flow through the
    custom_vjp dx which the grads suite covers."""
    from repro.models.quantize import quantize_weight

    key = jax.random.PRNGKey(5)
    K, N = 1024, 1024
    for M in (8, 2048):
        ks = jax.random.split(key, 2)
        x = jax.random.normal(ks[0], (M, K), jnp.float32)
        w = jax.random.normal(ks[1], (K, N), jnp.float32) * 0.1
        qw = quantize_weight(w, (-2,))
        q, scale = qw["q"], qw["scale"]
        for impl in _impls():
            kops.set_impl(impl)
            try:
                fwd = jax.jit(lambda x, q, scale: kops.quant_matmul(
                    x, q, scale, "mk,kn->mn"))
                fwd(x, q, scale).block_until_ready()
                tf = timeit(lambda: fwd(x, q, scale).block_until_ready(),
                            iters=10)
            finally:
                kops.set_impl("xla")
            rows.append(csv_row(
                f"kernels/quant_matmul/fwd/{impl}/M_{M}", tf * 1e6,
                f"K={K};N={N};int8",
            ))


def _bench_interpret_smoke(rows: list[str]) -> None:
    """One tiny fwd+bwd through the interpret tier: tracks that the
    differentiable Pallas path stays alive (timing is interpreter-bound)."""
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    M, d, dout, T, r = 128, 128, 128, 2, 8
    x = jax.random.normal(ks[0], (M // 64, 64, d), jnp.float32)
    a = jax.random.normal(ks[1], (T, d, r)) * 0.05
    b = jax.random.normal(ks[2], (T, r, dout)) * 0.05
    rt = jnp.asarray([0, 1], jnp.int32)
    scale = jnp.ones((T,))
    kops.set_impl("pallas_interpret")
    try:
        def loss(x, a, b):
            return kops.grouped_lora(x, a, b, rt, scale).sum()

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        jax.block_until_ready(bwd(x, a, b))
        tb = timeit(lambda: jax.block_until_ready(bwd(x, a, b)), iters=2)

        # mamba_scan: one tiny fwd+bwd through both backward kernels
        ks = jax.random.split(key, 5)
        B, S, H, dk, dv = 1, 128, 2, 16, 16
        q = jax.random.normal(ks[0], (B, S, H, dk), jnp.float32)
        kk = jax.random.normal(ks[1], (B, S, H, dk), jnp.float32) * 0.3
        v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
        la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
        li = jnp.zeros((B, S, H), jnp.float32)

        def mloss(q, kk, v, la):
            y, _ = kops.mamba_scan(q, kk, v, la, li, chunk=64)
            return (y ** 2).sum()

        mbwd = jax.jit(jax.grad(mloss, argnums=(0, 1, 2, 3)))
        jax.block_until_ready(mbwd(q, kk, v, la))
        tm = timeit(lambda: jax.block_until_ready(mbwd(q, kk, v, la)), iters=2)

        # decode_attention: fwd-only (serving path, never differentiated)
        ks = jax.random.split(key, 3)
        dq = jax.random.normal(ks[0], (2, 1, 4, 16), jnp.float32)
        dk = jax.random.normal(ks[1], (2, 64, 2, 16), jnp.float32)
        dv = jax.random.normal(ks[2], (2, 64, 2, 16), jnp.float32)
        dlen = jnp.asarray([40, 17], jnp.int32)
        dfwd = jax.jit(lambda q, k, v: kops.decode_attention(q, k, v, dlen))
        dfwd(dq, dk, dv).block_until_ready()
        td = timeit(lambda: dfwd(dq, dk, dv).block_until_ready(), iters=2)

        # quant_matmul: fwd-only (frozen int8 backbone side)
        from repro.models.quantize import quantize_weight
        ks = jax.random.split(key, 2)
        qx = jax.random.normal(ks[0], (64, 128), jnp.float32)
        qw = quantize_weight(
            jax.random.normal(ks[1], (128, 128), jnp.float32) * 0.1, (-2,))
        qfwd = jax.jit(lambda x: kops.quant_matmul(
            x, qw["q"], qw["scale"], "mk,kn->mn"))
        qfwd(qx).block_until_ready()
        tq = timeit(lambda: qfwd(qx).block_until_ready(), iters=2)
    finally:
        kops.set_impl("xla")
    rows.append(csv_row(
        "kernels/grouped_lora/fwd_bwd/pallas_interpret/smoke", tb * 1e6,
        "correctness_tier=1",
    ))
    rows.append(csv_row(
        "kernels/mamba_scan/fwd_bwd/pallas_interpret/smoke", tm * 1e6,
        "correctness_tier=1",
    ))
    rows.append(csv_row(
        "kernels/decode_attention/fwd/pallas_interpret/smoke", td * 1e6,
        "correctness_tier=1",
    ))
    rows.append(csv_row(
        "kernels/quant_matmul/fwd/pallas_interpret/smoke", tq * 1e6,
        "correctness_tier=1",
    ))


def run() -> list[str]:
    rows: list[str] = []
    _bench_grouped_lora(rows)
    _bench_packed_attention(rows)
    _bench_mamba_scan(rows)
    _bench_decode_attention(rows)
    _bench_quant_matmul(rows)
    _bench_interpret_smoke(rows)
    return rows
