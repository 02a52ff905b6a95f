"""§Perf optimization variants: striped-CP attention and A2A MoE.

Single-device equivalence runs inline; multi-device shard_map equivalence
runs in a subprocess with 8 forced host devices (tests otherwise keep the
default single-device platform)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import packed_attention_ref
from repro.models.cp_attention import (
    inverse_permutation,
    stripe_permutation,
    striped_cp_attention,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_stripe_permutation_roundtrip():
    perm = stripe_permutation(256, 16, 4)
    inv = inverse_permutation(perm)
    np.testing.assert_array_equal(perm[inv], np.arange(256))
    # block g of the contiguous layout lands contiguously on rank g%P
    blk = perm[:64]  # rank 0's slice start: blocks 0,4,8,12
    assert blk[0] == 0 and blk[16] == 4 * 16


def test_striped_cp_single_device_matches_ref(key):
    B, S, H, Hkv, dh = 2, 128, 4, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ref = packed_attention_ref(q, k, v, None, None, True)
    out = striped_cp_attention(q, k, v, pos, None, None, block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_striped_cp_packed_segments(key):
    B, S = 1, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, 2, 16), jnp.float32)
    half = S // 2
    seg = jnp.concatenate([jnp.zeros((B, half), jnp.int32),
                           jnp.ones((B, half), jnp.int32)], axis=1)
    pos = jnp.broadcast_to(
        jnp.concatenate([jnp.arange(half), jnp.arange(half)]).astype(jnp.int32), (B, S))
    ref = packed_attention_ref(q, k, v, seg, pos, True)
    out = striped_cp_attention(q, k, v, pos, seg, None, block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.kernels.ref import packed_attention_ref
    from repro.models.cp_attention import (striped_cp_attention,
                                           stripe_permutation, inverse_permutation)
    from repro.models.moe import moe_apply, moe_spec
    from repro.models.layers import materialize
    from repro.distributed.sharding import ShardingRules, activate_rules
    from repro.configs import smoke_config

    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)

    # striped CP attention
    B, S, H, Hkv, dh, blk = 2, 256, 4, 2, 16, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    ref = packed_attention_ref(q, k, v, None, None, True)
    perm = stripe_permutation(S, blk, 4)
    inv = inverse_permutation(perm)
    pos = jnp.broadcast_to(jnp.asarray(perm, jnp.int32), (B, S))
    fn = jax.jit(lambda q,k,v,p: striped_cp_attention(q,k,v,p,None,mesh,axis="model",block=blk))
    out = np.asarray(fn(q[:, perm], k[:, perm], v[:, perm], pos))[:, inv]
    np.testing.assert_allclose(out, np.asarray(ref), rtol=3e-4, atol=3e-4)

    # a2a MoE vs oracle
    cfg = smoke_config("deepseek-moe-16b").with_overrides(
        d_model=32, num_experts=8, top_k=2, expert_d_ff=16, capacity_factor=8.0)
    p = materialize(moe_spec(cfg), key)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32) * 0.5
    with activate_rules(None, None):
        y0, _ = moe_apply(p, x, cfg)
    for extra in ({"moe_impl": "a2a"}, {"moe_impl": "a2a", "moe_fsdp": "data"}):
        rules = ShardingRules().with_updates(batch=("data",), experts="model", **extra)
        with activate_rules(mesh, rules):
            y, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=2e-4, atol=2e-4)
    print("SUBPROC_OK")
""")


@pytest.mark.slow
def test_multi_device_variants_subprocess():
    # the child forces 8 host devices; it must never reach for a chip
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
