"""Gradient compression + sharding rules + pipeline reference tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.distributed.collectives import (
    compression_error,
    dequantize_int8,
    int8_psum,
    psum_tree,
    quantize_int8,
)
from repro.distributed.pipeline import pipeline_reference
from repro.distributed.sharding import ShardingRules, logical_to_spec


def test_int8_roundtrip_error_bound(key):
    x = jax.random.normal(key, (10_000,)) * 3.0
    err = float(compression_error(x))
    assert err < 0.01  # blockwise absmax int8: <1% L2 error on gaussians


def test_quantize_shapes(key):
    x = jax.random.normal(key, (1000,))
    q, s = quantize_int8(x, block=256)
    assert q.shape == (4, 256) and s.shape == (4, 1)
    back = dequantize_int8(q, s, 1000)
    assert back.shape == (1000,)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=0.05)


def test_int8_psum_single_device(key):
    """With axis size 1, the quantized psum == local dequantized value."""
    mesh = make_mesh((1,), ("d",))
    x = jax.random.normal(key, (512,))

    out = jax.shard_map(
        lambda v: int8_psum(v, "d"), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False,
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.05)


def test_psum_tree_compressed(key):
    mesh = make_mesh((1,), ("d",))
    tree = {"a": jax.random.normal(key, (64, 8)), "b": jax.random.normal(key, (17,))}
    out = jax.shard_map(
        lambda t: psum_tree(t, "d", compress=True), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False,
    )(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.08)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def test_logical_to_spec_dedup():
    rules = ShardingRules().with_updates(batch="model", seq="model")
    spec = logical_to_spec(("batch", "seq", None), rules)
    # "model" used once; the second claim falls back to replicated
    assert spec == P("model", None, None)


def test_rules_mesh_axes_filter():
    import jax

    mesh = make_mesh((1,), ("data",))
    r = ShardingRules().mesh_axes(mesh)
    assert r.lookup("batch") == ("data",)
    assert r.lookup("ff") is None  # "model" absent from this mesh


def test_rules_for_decode_cache_layout():
    from repro.configs import SHAPES, get_config
    from repro.launch.rules import rules_for
    import jax

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("yi-34b")
    r = rules_for(cfg, SHAPES["decode_32k"], mesh)
    assert r.lookup("seq") is None  # decode: no seq sharding of 1-token input


def test_opt_shardings_task_axis():
    """Optimizer moments are sharded along the adapter-stack TASK axis over
    the data-parallel mesh axis (ROADMAP item: moments were replicated)."""
    from repro.configs import smoke_config
    from repro.launch.steps import opt_shardings
    from repro.peft.adapters import LORA
    from repro.peft.methods import AdapterConfig
    from repro.peft.multitask import MultiTaskAdapters
    from repro.train.optimizer import adamw_init

    cfg = smoke_config("llama3.2-3b")
    mta = MultiTaskAdapters(cfg, [AdapterConfig(LORA, rank=4)] * 2)
    opt_specs = jax.eval_shape(adamw_init, mta.abstract())
    # abstract mesh: spec construction needs no physical 2-device host
    mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))

    shard = opt_shardings(opt_specs, mesh, mta=mta, cfg=cfg)
    # dense family: adapter leaves are [layers, tasks, ...] -> task axis 1
    for tree in (shard.m, shard.v):
        spec = tree["lora"]["attn_q"]["a"].spec
        assert spec[1] == "data", spec
        assert all(s is None for i, s in enumerate(spec) if i != 1), spec
    # step scalar stays replicated
    assert shard.step.spec == P()
    # structure matches the specs tree (None moment leaves stay None)
    jax.tree.map(lambda a, b: None, opt_specs, shard)

    # legacy path (no mta): fully replicated
    rep = opt_shardings(opt_specs, mesh)
    assert rep.m["lora"]["attn_q"]["a"].spec == P()

    # a task count that doesn't divide the mesh axis falls back to replicated
    mta3 = MultiTaskAdapters(cfg, [AdapterConfig(LORA, rank=4)] * 3)
    opt3 = jax.eval_shape(adamw_init, mta3.abstract())
    shard3 = opt_shardings(opt3, mesh, mta=mta3, cfg=cfg)
    assert shard3.m["lora"]["attn_q"]["a"].spec == P()


# ---------------------------------------------------------------------------
# pipeline reference semantics
# ---------------------------------------------------------------------------


def test_pipeline_reference_matches_direct(key):
    """Clock-loop pipeline output == sequential stage composition."""
    n_stages, n_micro, mb, d = 4, 6, 2, 8
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
    micro = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = pipeline_reference(stage_fn, ws, micro, n_stages)
    # direct composition
    expect = micro
    for s in range(n_stages):
        expect = jax.vmap(lambda x: stage_fn(ws[s], x))(expect)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5)


def test_pipeline_reference_differentiable(key):
    n_stages, n_micro, mb, d = 2, 3, 2, 4
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
    micro = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def loss(ws):
        out = pipeline_reference(lambda w, x: jnp.tanh(x @ w), ws, micro, n_stages)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(ws)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0
