"""The five Pallas kernels compile for a TPU v5e at the widths the main path
runs, without a chip: the TPU compiler lowers each one for a described
``v5e:2x2`` topology.  A block the chip's tiling rule refuses, or a kernel
that needs more scoped VMEM than the default, fails here.

Also: the compile-cache helper every entry point calls.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.cost_model import V5E
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.grouped_lora import grouped_lora_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.packed_attention import packed_attention_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.launch import compile_cache

# smollm-360m: d_model 960, 15 query / 5 kv heads of 64, d_ff 2560
SMOLLM = dict(B=2, S=2048, H=15, Hkv=5, dh=64)
# zamba2-2.7b's mamba mixer: 80 heads, dk = dv = 64
ZAMBA2 = dict(B=1, S=2048, H=80, d=64)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas_tier():
    prev = kops.get_impl()
    kops.set_impl("pallas")
    yield
    kops.set_impl(prev)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E.hbm_bytes, used
    return compiled


def _grads(fn, argnums):
    """fwd+bwd of a scalar loss over ``fn``'s (first) output."""
    def loss(*args):
        out = fn(*args)
        return sum(o.astype(jnp.float32).sum() for o in jax.tree.leaves(out))

    return jax.grad(loss, argnums=argnums)


@pytest.mark.parametrize("d_in", [960, 2560])
def test_grouped_lora_compiles(one_chip, d_in):
    M, r, T, d_out = 4096, 16, 4, 960

    def fwd_bwd(x, a, b, rows, scale):
        return _grads(lambda x, a, b, s: grouped_lora_pallas(x, a, b, rows, s),
                      (0, 1, 2, 3))(x, a, b, scale)

    _compile_for_chip(
        fwd_bwd, _spec(one_chip, (M, d_in)), _spec(one_chip, (T, d_in, r)),
        _spec(one_chip, (T, r, d_out)), _spec(one_chip, (M,), jnp.int32),
        _spec(one_chip, (T,), jnp.float32))


def test_packed_attention_compiles(one_chip):
    B, S, H, Hkv, dh = (SMOLLM[k] for k in ("B", "S", "H", "Hkv", "dh"))
    qkv = (_spec(one_chip, (B, S, H, dh)), _spec(one_chip, (B, S, Hkv, dh)),
           _spec(one_chip, (B, S, Hkv, dh)))
    _compile_for_chip(packed_attention_pallas, *qkv)
    _compile_for_chip(_grads(packed_attention_pallas, (0, 1, 2)), *qkv)


def test_packed_attention_prefix_rows_compile(one_chip, pallas_tier):
    B, S, H, Hkv, dh = (SMOLLM[k] for k in ("B", "S", "H", "Hkv", "dh"))
    P = 16  # prefix-tuning rows: padded to a k-tile inside ops

    def attn(q, k, v, pk, pv, keep):
        return _grads(lambda q, k, v, pk, pv: kops.packed_attention(
            q, k, v, prefix_kv=(pk, pv), prefix_keep=keep),
            (0, 1, 2, 3, 4))(q, k, v, pk, pv)

    _compile_for_chip(
        attn, _spec(one_chip, (B, S, H, dh)), _spec(one_chip, (B, S, Hkv, dh)),
        _spec(one_chip, (B, S, Hkv, dh)), _spec(one_chip, (B, P, Hkv, dh)),
        _spec(one_chip, (B, P, Hkv, dh)), _spec(one_chip, (B, P), jnp.float32))


def test_decode_attention_compiles(one_chip):
    B, Smax, H, Hkv, dh = 8, 2048, SMOLLM["H"], SMOLLM["Hkv"], SMOLLM["dh"]
    _compile_for_chip(
        decode_attention_pallas, _spec(one_chip, (B, 1, H, dh)),
        _spec(one_chip, (B, Smax, Hkv, dh)), _spec(one_chip, (B, Smax, Hkv, dh)),
        _spec(one_chip, (B,), jnp.int32), _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("with_reset", [False, True])
def test_mamba_scan_compiles(one_chip, with_reset):
    B, S, H, d = (ZAMBA2[k] for k in ("B", "S", "H", "d"))
    args = [_spec(one_chip, (B, S, H, d))] * 3 + [
        _spec(one_chip, (B, S, H), jnp.float32)] * 2
    if with_reset:
        args.append(_spec(one_chip, (B, S), jnp.float32))

    def fwd_bwd(q, k, v, la, li, *reset):
        def scan(q, k, v, la, li):
            return mamba_scan_pallas(q, k, v, la, li,
                                     reset=reset[0] if reset else None)

        return _grads(scan, (0, 1, 2, 3, 4))(q, k, v, la, li)

    _compile_for_chip(fwd_bwd, *args)


@pytest.mark.parametrize("mkn", [(4096, 960, 2560), (4096, 2560, 10240),
                                 (8, 2560, 10240)])
def test_quant_matmul_compiles(one_chip, mkn):
    M, K, N = mkn

    def fwd_bwd(x, q, s):
        y, vjp = jax.vjp(lambda x: quant_matmul_pallas(x, q, s), x)
        return y, vjp(jnp.ones_like(y))

    _compile_for_chip(fwd_bwd, _spec(one_chip, (M, K)),
                      _spec(one_chip, (K, N), jnp.int8),
                      _spec(one_chip, (N,), jnp.float32))


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_respects_env(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_defaults_into_checkout(monkeypatch, cache_dir_config):
    import pathlib

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.configure_compile_cache()
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert got == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.configure_compile_cache() == got  # fixed, not per call
