"""End-to-end behaviour tests for the MuxTune system (fixed-data training,
dynamic task registration, per-task isolation, engine throughput path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import ExecutionPlanner, ModelGenerator, ParallelismSpec, PEFTEngine
from repro.data import HTaskLoader, make_task
from repro.peft.adapters import ADAPTER_TUNING, IA3, LORA
from repro.peft.methods import AdapterConfig
from repro.peft.multitask import MultiTaskAdapters, TaskSegments
from repro.train.optimizer import adamw_init, adamw_update, apply_updates

CFG = smoke_config("llama3.2-3b")


def _tasks():
    return [
        make_task("t0", "sst2", 2, AdapterConfig(LORA, rank=4), seed=0),
        make_task("t1", "qa", 2, AdapterConfig(LORA, rank=8), seed=1),
        make_task("t2", "rte", 1, AdapterConfig(ADAPTER_TUNING, rank=4), seed=2),
    ]


def test_engine_trains_on_fixed_batch(key):
    """On a FIXED batch, multi-task loss must decrease."""
    from repro.models.transformer import build_model

    tasks = [AdapterConfig(LORA, rank=8), AdapterConfig(LORA, rank=8)]
    m = build_model(CFG)
    params = m.init(key)
    mta = MultiTaskAdapters(CFG, tasks)
    seg = TaskSegments.contiguous([2, 2])
    ad = mta.init(jax.random.PRNGKey(1))
    opt = adamw_init(ad)
    ctxf = mta.ctx_factory(seg)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, CFG.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(7), (4, 32), 0, CFG.vocab_size),
        "loss_mask": jnp.ones((4, 32), jnp.float32),
    }

    @jax.jit
    def step(ad, opt):
        def loss_fn(ad):
            out = m.forward(params, batch, adapters=ad, ctx_factory=ctxf)
            return seg.per_task_loss(out["per_token_loss"], batch["loss_mask"]).sum()

        loss, g = jax.value_and_grad(loss_fn, allow_int=True)(ad)
        upd, opt = adamw_update(g, opt, ad, lr=5e-3)
        return apply_updates(ad, upd), opt, loss

    losses = []
    for _ in range(8):
        ad, opt, loss = step(ad, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.01, losses


def test_planner_engine_iteration():
    tasks = _tasks()
    planner = ExecutionPlanner(CFG, ParallelismSpec(num_stages=2, chips_per_stage=1))
    plan = planner.plan(tasks, n_micro=1)
    gen = ModelGenerator(CFG)
    gen.register_tasks(tasks)
    eng = PEFTEngine(gen, plan, lr=1e-3)
    loaders = {i: HTaskLoader(tasks, plan.alignment[i], CFG.vocab_size)
               for i in range(len(plan.htasks))}
    m = eng.run_iteration(loaders)
    assert np.isfinite(m.loss)
    assert m.tokens > 0 and m.effective_tokens > 0
    assert m.effective_tokens <= m.tokens
    tp = eng.throughput(m)
    assert tp["tokens_per_s"] > 0


def test_register_tasks_preserves_existing_adapters():
    tasks = _tasks()
    gen = ModelGenerator(CFG)
    reg1 = gen.register_tasks(tasks)
    a0 = reg1.adapter_params["lora"]["attn_q"]["a"]
    sentinel = jnp.full_like(a0, 3.0)
    reg1.adapter_params["lora"]["attn_q"]["a"] = sentinel
    t_new = make_task("t9", "qa", 1, AdapterConfig(LORA, rank=8), seed=9)
    reg2 = gen.register_tasks([t_new])
    assert len(reg2.tasks) == 4
    a_new = reg2.adapter_params["lora"]["attn_q"]["a"]
    # surviving task slots carry their old values into the rebuilt stack
    np.testing.assert_allclose(np.asarray(a_new[:, 0], np.float32), 3.0)


def test_deregister_tasks():
    tasks = _tasks()
    gen = ModelGenerator(CFG)
    gen.register_tasks(tasks)
    reg = gen.deregister_tasks(["t1"])
    assert [t.task_id for t in reg.tasks] == ["t0", "t2"]


def test_per_task_loss_isolation(key):
    """Eq. 1-2: fused multi-task forward == independent per-task forwards."""
    from repro.models.transformer import build_model

    m = build_model(CFG)
    params = m.init(key)
    tasks = [AdapterConfig(LORA, rank=4), AdapterConfig(LORA, rank=4)]
    mta = MultiTaskAdapters(CFG, tasks)
    seg = TaskSegments.contiguous([2, 2])
    ad = mta.init(jax.random.PRNGKey(1))
    ad["lora"]["attn_q"]["b"] = jax.random.normal(
        jax.random.PRNGKey(2), ad["lora"]["attn_q"]["b"].shape,
        ad["lora"]["attn_q"]["b"].dtype) * 0.1
    ctxf = mta.ctx_factory(seg)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, CFG.vocab_size),
        "labels": jax.random.randint(key, (4, 32), 0, CFG.vocab_size),
        "loss_mask": jnp.ones((4, 32), jnp.float32),
    }
    fused = m.forward(params, batch, adapters=ad, ctx_factory=ctxf)["per_token_loss"]

    for t, rows in ((0, slice(0, 2)), (1, slice(2, 4))):
        sub = {k: v[rows] for k, v in batch.items()}
        seg1 = TaskSegments((t, t), 2)
        ctx1 = mta.ctx_factory(seg1)
        solo = m.forward(params, sub, adapters=ad, ctx_factory=ctx1)["per_token_loss"]
        np.testing.assert_allclose(
            np.asarray(fused[rows], np.float32), np.asarray(solo, np.float32),
            rtol=3e-3, atol=3e-3,
        )


def test_nan_guard_isolates_diverging_task():
    """A non-finite loss must not poison optimizer state (engine guard)."""
    tasks = _tasks()[:2]
    planner = ExecutionPlanner(CFG, ParallelismSpec(num_stages=1, chips_per_stage=1))
    plan = planner.plan(tasks, n_micro=1)
    gen = ModelGenerator(CFG)
    gen.register_tasks(tasks)
    eng = PEFTEngine(gen, plan, lr=1e-3)
    eng.reg.adapter_params["lora"]["attn_q"]["a"] = (
        eng.reg.adapter_params["lora"]["attn_q"]["a"].at[0, 0].set(jnp.inf)
    )
    loaders = {i: HTaskLoader(tasks, plan.alignment[i], CFG.vocab_size)
               for i in range(len(plan.htasks))}
    eng.run_iteration(loaders)
    # adapters themselves must not have been moved by a NaN update
    ad = eng.reg.adapter_params["lora"]["attn_q"]["b"]
    assert np.isfinite(np.asarray(ad, np.float32)).all()


def test_token_stream_same_in_every_process():
    """The same command trains on the same tokens in every interpreter,
    whatever its string-hash salt."""
    import os
    import subprocess
    import sys

    code = ("import itertools; from repro.data.synthetic import token_stream; "
            "print(list(itertools.islice(token_stream('sst2-t0', 49152, 3), 32)))")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    outs = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONPATH=src, PYTHONHASHSEED=salt,
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        outs.append(out.stdout)
    assert outs[0] == outs[1]
