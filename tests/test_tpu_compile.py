"""The five Pallas kernels compile for a TPU v5e at the widths the main path
runs, without a chip: the TPU compiler lowers each one for a described
``v5e:2x2`` topology.  A block the chip's tiling rule refuses, or a kernel
that needs more scoped VMEM than the default, fails here.

Also: the compile-cache helper every entry point calls.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.cost_model import V5E
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.grouped_lora import grouped_lora_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.packed_attention import (packed_attention_pallas,
                                            prefix_pad, tiles)
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.launch import compile_cache

# smollm-360m: d_model 960, 15 query / 5 kv heads of 64, d_ff 2560
SMOLLM = dict(B=2, S=2048, H=15, Hkv=5, dh=64)
# zamba2-2.7b's mamba mixer: 80 heads, dk = dv = 64
ZAMBA2 = dict(B=1, S=2048, H=80, d=64)
# granite-4.0-h-micro's training cell: 2 rows of 4096; Mamba-2 64 heads,
# dk = d_state 128, dv = head 64, chunk 256; attention 32 / 8 heads of 64
GRANITE = dict(B=2, S=4096, H=64, dk=128, dv=64, chunk=256, Hq=32, Hkv=8, dh=64)


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


def _compile_for_chip(fn, *args, names=()):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel"
    # each kernel's name reaches its HLO op_name, in a transform's
    # parentheses where the kernel is the outermost call under one
    found = set(re.findall(r'([A-Za-z0-9_]+)\)*/pallas_call"', text))
    assert set(names) <= found, (names, found)
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
        _spec(one_chip, (T,), jnp.float32),
        names=("grouped_lora_fwd", "grouped_lora_bwd"))


def test_packed_attention_compiles(one_chip):
    B, S, H, Hkv, dh = (SMOLLM[k] for k in ("B", "S", "H", "Hkv", "dh"))
    qkv = (_spec(one_chip, (B, S, H, dh)), _spec(one_chip, (B, S, Hkv, dh)),
           _spec(one_chip, (B, S, Hkv, dh)))
    attn = functools.partial(packed_attention_pallas, scale=dh ** -0.5)
    _compile_for_chip(attn, *qkv)
    _compile_for_chip(_grads(attn, (0, 1, 2)), *qkv,
                      names=("packed_attention_fwd", "packed_attention_dq",
                             "packed_attention_dkv"))


def test_packed_attention_prefix_rows_compile(one_chip, pallas_tier):
    B, S, H, Hkv, dh = (SMOLLM[k] for k in ("B", "S", "H", "Hkv", "dh"))
    P = 16  # prefix-tuning rows: padded to a k-tile inside ops

    def attn(q, k, v, pk, pv, keep):
        return _grads(lambda q, k, v, pk, pv: kops.packed_attention(
            q, k, v, prefix_kv=(pk, pv), prefix_keep=keep, scale=dh ** -0.5),
            (0, 1, 2, 3, 4))(q, k, v, pk, pv)

    _compile_for_chip(
        attn, _spec(one_chip, (B, S, H, dh)), _spec(one_chip, (B, S, Hkv, dh)),
        _spec(one_chip, (B, S, Hkv, dh)), _spec(one_chip, (B, P, Hkv, dh)),
        _spec(one_chip, (B, P, Hkv, dh)), _spec(one_chip, (B, P), jnp.float32))


@pytest.mark.parametrize("B,S,H,Hkv,dh,P,kv_tile", [
    (16, 1024, 15, 5, 64, 0, 512),   # smollm-360m's training step
    (16, 1024, 15, 5, 64, 16, 384),  # with 16 prefix-tuning rows
    (1, 4096, 32, 8, 128, 0, 512),   # long and wide: 32/8 heads of 128
])
def test_packed_attention_train_tiles_compile(one_chip, pallas_tier, B, S, H,
                                              Hkv, dh, P, kv_tile):
    """fwd, dq and dkv at the model's 512-row q tile: the kv tile follows
    it, and the (512 x 512) f32 temporaries fit the default scoped VMEM."""
    block_q = 512
    assert tiles(S, S + P + (prefix_pad(S, P) if P else 0), block_q) == (
        block_q, kv_tile)
    names = ("packed_attention_fwd", "packed_attention_dq",
             "packed_attention_dkv")
    qkv = (_spec(one_chip, (B, S, H, dh)), _spec(one_chip, (B, S, Hkv, dh)),
           _spec(one_chip, (B, S, Hkv, dh)))
    if not P:
        _compile_for_chip(_grads(lambda q, k, v: kops.packed_attention(
            q, k, v, block_q=block_q, scale=dh ** -0.5), (0, 1, 2)), *qkv,
            names=names)
        return

    def attn(q, k, v, pk, pv, keep):
        return _grads(lambda q, k, v, pk, pv: kops.packed_attention(
            q, k, v, prefix_kv=(pk, pv), prefix_keep=keep, block_q=block_q,
            scale=dh ** -0.5),
            (0, 1, 2, 3, 4))(q, k, v, pk, pv)

    _compile_for_chip(attn, *qkv, _spec(one_chip, (B, P, Hkv, dh)),
                      _spec(one_chip, (B, P, Hkv, dh)),
                      _spec(one_chip, (B, P), jnp.float32), names=names)


def test_decode_attention_compiles(one_chip):
    B, Smax, H, Hkv, dh = 8, 2048, SMOLLM["H"], SMOLLM["Hkv"], SMOLLM["dh"]
    _compile_for_chip(
        decode_attention_pallas, _spec(one_chip, (B, 1, H, dh)),
        _spec(one_chip, (B, Smax, Hkv, dh)), _spec(one_chip, (B, Smax, Hkv, dh)),
        _spec(one_chip, (B,), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        names=("decode_attention",))


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

    _compile_for_chip(fwd_bwd, *args, names=(
        "mamba_scan_fwd", "mamba_scan_bwd_state", "mamba_scan_bwd_chunk"))


@pytest.mark.parametrize("with_reset", [False, True])
def test_granite_mamba_scan_compiles(one_chip, with_reset):
    """Forward and both backward kernels at granite's widths: the state
    tile is (128, 64) f32, the chunk (256, 128) / (256, 64).  The training
    cell carries a reset row (1.0 at each row's first position), so the
    backward takes the segment-bounded ``dla`` sum: it compiles to matmuls
    against masks, with no gather or scatter around the kernels."""
    g = GRANITE
    B, S, H = g["B"], g["S"], g["H"]
    args = [_spec(one_chip, (B, S, H, g["dk"]))] * 2 + [
        _spec(one_chip, (B, S, H, g["dv"]))] + [
        _spec(one_chip, (B, S, H), jnp.float32)] * 2
    if with_reset:
        args.append(_spec(one_chip, (B, S), jnp.float32))

    def fwd_bwd(q, k, v, la, li, *reset):
        def scan(q, k, v, la, li):
            return mamba_scan_pallas(q, k, v, la, li, chunk=g["chunk"],
                                     reset=reset[0] if reset else None)

        return _grads(scan, (0, 1, 2, 3, 4))(q, k, v, la, li)

    text = _compile_for_chip(fwd_bwd, *args, names=(
        "mamba_scan_fwd", "mamba_scan_bwd_state",
        "mamba_scan_bwd_chunk")).as_text()
    assert not re.findall(r"\b(?:gather|scatter)\(", text)


def test_granite_attention_compiles_with_its_scale(one_chip, pallas_tier):
    g = GRANITE
    B, S = g["B"], g["S"]
    qkv = (_spec(one_chip, (B, S, g["Hq"], g["dh"])),
           _spec(one_chip, (B, S, g["Hkv"], g["dh"])),
           _spec(one_chip, (B, S, g["Hkv"], g["dh"])))
    _compile_for_chip(_grads(lambda q, k, v: kops.packed_attention(
        q, k, v, block_q=512, scale=1.0 / 64), (0, 1, 2)), *qkv,
        names=("packed_attention_fwd", "packed_attention_dq",
               "packed_attention_dkv"))


@pytest.mark.parametrize("d_in,d_out", [(2048, 8512), (4096, 2048)])
def test_granite_grouped_lora_compiles(one_chip, d_in, d_out):
    """Rank 16 on ``ssm_in`` (d_out 8512, not a multiple of 128: the kernel
    takes d_out whole) and ``ssm_out``, over the cell's 8192 tokens."""
    M, r, T = GRANITE["B"] * GRANITE["S"], 16, 1

    def fwd_bwd(x, a, b, rows, scale):
        return _grads(lambda x, a, b, s: grouped_lora_pallas(x, a, b, rows, s),
                      (0, 1, 2, 3))(x, a, b, scale)

    _compile_for_chip(
        fwd_bwd, _spec(one_chip, (M, d_in)), _spec(one_chip, (T, d_in, r)),
        _spec(one_chip, (T, r, d_out)), _spec(one_chip, (M,), jnp.int32),
        _spec(one_chip, (T,), jnp.float32),
        names=("grouped_lora_fwd", "grouped_lora_bwd"))


@pytest.mark.parametrize("mkn", [(4096, 960, 2560), (4096, 2560, 10240),
                                 (8, 2560, 10240)])
def test_quant_matmul_compiles(one_chip, mkn):
    M, K, N = mkn

    def fwd_bwd(x, q, s):
        y, vjp = jax.vjp(lambda x: quant_matmul_pallas(x, q, s), x)
        return y, vjp(jnp.ones_like(y))

    _compile_for_chip(fwd_bwd, _spec(one_chip, (M, K)),
                      _spec(one_chip, (K, N), jnp.int8),
                      _spec(one_chip, (N,), jnp.float32), names=("quant_matmul",))


def test_train_step_names_its_kernels_and_phases(one_chip, pallas_tier):
    """The engine's training step, compiled for the chip, names every
    kernel it runs in the instruction names the device trace shows, and
    its phases in the op_name metadata the phase readers join to them
    (two layers of smollm-360m at full width)."""
    import re

    from repro.configs import get_config
    from repro.core import (ExecutionPlanner, ModelGenerator, ParallelismSpec,
                            PEFTEngine)
    from repro.data import HTaskLoader, make_task
    from repro.peft.adapters import LORA
    from repro.peft.methods import AdapterConfig

    cfg = get_config("smollm-360m").with_overrides(num_layers=2, vocab_size=1024)
    tasks = [make_task("t0", "sst2", 2, AdapterConfig(
        LORA, rank=16, targets=("attn_q", "attn_k", "attn_v", "attn_o")), seed=0)]
    plan = ExecutionPlanner(cfg, ParallelismSpec()).plan(tasks, n_micro=1)
    gen = ModelGenerator(cfg)
    gen.register_tasks(tasks)
    eng = PEFTEngine(gen, plan, lr=1e-3)
    batch = next(HTaskLoader(tasks, plan.alignment[0], cfg.vocab_size))
    n_acc = max(len(plan.tasks), sum(eng.reg.mta.kind_capacity.values()))
    args = (eng.backbone, eng.reg.adapter_params, eng.reg.opt_state,
            eng._slot_steps, batch, eng._member_ids[0],
            (jnp.float32(0), jnp.zeros((n_acc,), jnp.float32)))
    specs = jax.tree.map(lambda a: _spec(one_chip, jnp.shape(a), jnp.result_type(a)),
                         args)
    text = eng._make_step(0).lower(*specs).compile().as_text()
    scopes = set(re.findall(r'op_name="([^"]+)"', text))
    want = {"packed_attention_fwd", "packed_attention_dq", "packed_attention_dkv",
            "grouped_lora_fwd", "grouped_lora_bwd"}
    assert set(re.findall(r'([A-Za-z0-9_]+)\)*/pallas_call"', text)) == want
    calls = re.findall(r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert {re.sub(r"(\.\d+)+$", "", c) for c in calls} == want
    assert any("/jvp(train.forward)/" in s for s in scopes)
    assert any("/transpose(jvp(train.forward))/" in s for s in scopes)
    assert any("/train.optimizer/" in s for s in scopes)


def test_granite_step_spans_its_mamba_mixers(one_chip, pallas_tier):
    """granite-4.0-h-micro's training step at full width (one Mamba and one
    attention layer): every kernel it runs is named, and the scan kernels,
    forward and backward, carry the ``mixer.mamba`` scope in the op_names
    that the device-trace readers join to them."""
    from repro.configs import get_config
    from repro.core import (ExecutionPlanner, ModelGenerator, ParallelismSpec,
                            PEFTEngine)
    from repro.data import HTaskLoader, make_task
    from repro.peft.adapters import LORA
    from repro.peft.methods import AdapterConfig

    cfg = get_config("granite-4.0-h-micro").with_overrides(
        num_layers=2, layer_pattern=("mamba", "attention"), vocab_size=1024)
    tasks = [make_task("t0", "sst2", 2, AdapterConfig(
        LORA, rank=16, targets=("ssm_in", "ssm_out", "attn_q", "attn_k",
                                "attn_v", "attn_o")), seed=0)]
    plan = ExecutionPlanner(cfg, ParallelismSpec()).plan(tasks, n_micro=1)
    gen = ModelGenerator(cfg)
    gen.register_tasks(tasks)
    eng = PEFTEngine(gen, plan, lr=1e-3)
    batch = next(HTaskLoader(tasks, plan.alignment[0], cfg.vocab_size))
    n_acc = max(len(plan.tasks), sum(eng.reg.mta.kind_capacity.values()))
    args = (eng.backbone, eng.reg.adapter_params, eng.reg.opt_state,
            eng._slot_steps, batch, eng._member_ids[0],
            (jnp.float32(0), jnp.zeros((n_acc,), jnp.float32)))
    specs = jax.tree.map(lambda a: _spec(one_chip, jnp.shape(a), jnp.result_type(a)),
                         args)
    text = eng._make_step(0).lower(*specs).compile().as_text()
    scan = {"mamba_scan_fwd", "mamba_scan_bwd_state", "mamba_scan_bwd_chunk"}
    want = scan | {"packed_attention_fwd", "packed_attention_dq",
                   "packed_attention_dkv", "grouped_lora_fwd", "grouped_lora_bwd"}
    found = set(re.findall(r'([A-Za-z0-9_]+)\)*/pallas_call"', text))
    assert found == want, found
    kernel_scopes = re.findall(r'op_name="([^"]*?([A-Za-z0-9_]+)\)*/pallas_call)"', text)
    for k in scan:
        ops = [s for s, name in kernel_scopes if name == k]
        assert ops and all("mixer.mamba" in s for s in ops), (k, ops)
    assert any("transpose(" in s and "mixer.mamba" in s for s, _ in kernel_scopes)


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
