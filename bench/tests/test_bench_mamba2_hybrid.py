"""The ``mamba2_hybrid`` block family (``bench/blocks/mamba2_hybrid.py``):
a tiny Mamba-2 hybrid cell in granite-4.0-h-micro's layout (one period of
``[mamba, mamba, attention, mamba]``, d 64, 4 Mamba heads of 16, state 16,
chunk 16, vocabulary 256, granite's multipliers) goes through the
benchmark's entry points, the service's training agrees with the plain
reference on the ``xla`` and ``pallas_interpret`` tiers, and each of the
mechanisms granite adds to the block moves the comparison past its limits
when the reference leaves it out.  Also: the reference's chunked scan is
the step-by-step recurrence, and the full-size cell's work counts are
pinned."""
import json
import os
import sys
import time

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import blocks  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402
import refcheck  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic as tr  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402

FIXTURE = harness.Path(HERE) / "fixtures" / "hybrid"
CELL = "tiny-hybrid.train"
SEED = 2**31 + 17
GRANITE = "granite-4.0-h-micro.train-lora16-4k"


@pytest.fixture
def tier():
    prev = kops.get_impl()
    yield kops.set_impl
    kops.set_impl(prev)


def test_granite_work_counts_are_pinned():
    """What ``mfu.train`` and the scan rooflines read for the new cell."""
    cell = harness.find_cell(GRANITE)
    ts = tr.tenants(cell.traffic, cell.cfg)
    fam = blocks.family(cell.cfg)
    assert work.matmul_params(cell.cfg) == 3_190_292_480
    assert work.train_flops(cell.cfg, ts) == 109_519_833_137_152.0
    assert fam.scan_work(cell.cfg, 8192) == {
        "fwd_flops": 938_622_320_640.0, "fwd_bytes": 5_133_828_096.0,
        "bwd_flops": 1_877_244_641_280.0, "bwd_bytes": 7_851_737_088.0}


def test_the_file_is_the_programs_granite():
    """The configuration file's program arch runs every published width,
    the layer pattern and the multipliers; an arch that differs in any of
    them is refused."""
    cell = harness.find_cell(GRANITE)
    ac = harness.arch_config(cell.cfg)
    assert ac.name == "granite-4.0-h-micro"
    assert list(ac.layer_pattern) * ac.n_periods == cell.cfg["layer_types"]
    for key, value in [("residual_multiplier", 1.0), ("positional", "rope"),
                       ("ssm_conv_bias", False), ("attention_scale", 0.0),
                       ("ssm_state", 64), ("layer_pattern", ["mamba"] * 10)]:
        cfg = json.loads(json.dumps(cell.cfg))
        cfg["program"]["overrides"] = {key: value}
        with pytest.raises(RuntimeError, match="does not run the file"):
            blocks.family(cfg).program_config(cfg)


def test_entry_points_reach_the_family():
    cell = harness.find_cell(CELL, FIXTURE)
    fam = blocks.family(cell.cfg)
    assert harness.Path(fam.__file__) == (
        harness.ROOT / "bench" / "blocks" / "mamba2_hybrid.py").resolve()
    ac = harness.arch_config(cell.cfg)
    ts = tr.tenants(cell.traffic, cell.cfg)
    assert [t.targets for t in ts] == [fam.targets(cell.cfg, "mixers"),
                                       fam.targets(cell.cfg, "all")]

    from repro.models.transformer import build_model

    bb = weights.make_backbone(cell.cfg, SEED)
    weights.same_layout(weights.program_backbone(cell.cfg, bb),
                        build_model(ac).abstract_params(), "backbone")
    ads = weights.make_adapters(cell.cfg, ts, SEED, 0)
    # an MLP site sits on all four layers, the mixer sites on their kind's
    assert ads[1]["mlp_up"]["down"].shape[0] == 4
    assert ads[1]["ssm_in"]["down"].shape[0] == 3
    assert ads[1]["attn_q"]["down"].shape[0] == 1

    # the two adapter maps are inverse to each other
    stacks = {"lora": {s: {k: jnp.asarray(v)[:, None] for k, v in leaves.items()}
                       for s, leaves in ads[0].items()},
              "adapter": {s: {k: jnp.asarray(v)[:, None] for k, v in leaves.items()}
                          for s, leaves in ads[1].items()}}
    back = fam.from_program(cell.cfg, fam.to_program(cell.cfg, stacks))
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                                     back, stacks))

    batch = reference.sequences_batch(
        [{"tokens": list(range(1, 65)), "last_label_zero": False}])
    loss = reference.Reference(cell.cfg).tenant_loss(
        ads[0], bb, batch, ts[0].method, ts[0].scale)
    assert np.isfinite(float(loss))
    assert work.train_flops(cell.cfg, ts) > 0


def test_chunked_scan_is_the_recurrence():
    """``ssd`` (what the reference's stack runs) against ``recurrence``
    (the equation one time step after another), values and gradients, in
    float32 with chunks that do not divide the decay evenly."""
    cell = harness.find_cell(CELL, FIXTURE)
    fam = blocks.family(cell.cfg)
    ref = reference.Reference(cell.cfg)
    N, S, nh, P, St = 2, 48, 3, 5, 7
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (N, S, nh, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (N, S, nh)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (nh,), minval=0.0, maxval=2.0))
    B = jax.random.normal(k[3], (N, S, St))
    C = jax.random.normal(k[4], (N, S, St))
    g = jax.random.normal(k[5], (N, S, nh, P))

    def loss(f, *args):
        return jnp.sum(f(*args) * g)

    seq = lambda *a: fam.recurrence(ref, *a)            # noqa: E731
    for chunk in (16, 48):
        chk = lambda *a: fam.ssd(ref, *a, chunk)        # noqa: E731
        np.testing.assert_allclose(chk(x, dt, A, B, C), seq(x, dt, A, B, C),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *a: loss(chk, *a), argnums=(0, 1, 2, 3, 4))(
            x, dt, A, B, C)
        want = jax.grad(lambda *a: loss(seq, *a), argnums=(0, 1, 2, 3, 4))(
            x, dt, A, B, C)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_service_training_matches_the_reference(tier, impl):
    """The benchmark's own run of the fixture cell: set-up through the
    service, three training steps, the window, and the check against the
    float32 reference (loss, first gradient, change after three steps)."""
    tier(impl)
    res = run.execute(FIXTURE, CELL, SEED, 1.0, False,
                      t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.fixture(scope="module")
def first_step():
    """The program's first training step on the fixture cell (xla tier):
    its readings, for the comparisons below."""
    prev = kops.get_impl()
    kops.set_impl("xla")
    try:
        cell = harness.find_cell(CELL, FIXTURE)
        r = harness.build(cell, SEED)
        harness.train_readings(r, 1)
        harness.free_program(r)
    finally:
        kops.set_impl(prev)
    return r


def without(cfg, mechanism):
    """The configuration as a reference that leaves one mechanism out."""
    cfg = json.loads(json.dumps(cfg))
    cfg.update({"conv_bias": {"mamba_conv_bias": False},
                "nope": {"position_embedding_type": "rope"},
                "attention_multiplier": {"attention_multiplier": None},
                "residual_multiplier": {"residual_multiplier": 1.0},
                "embedding_multiplier": {"embedding_multiplier": 1.0},
                "logits_scaling": {"logits_scaling": 1.0}}[mechanism])
    return cfg


def first_step_gaps(r, cfg):
    """Loss and first-gradient gaps of the program's first step against a
    reference on ``cfg``, for the first tenant (LoRA on the mixers)."""
    cell = harness.Cell(**{**r.cell.__dict__, "cfg": cfg})
    t = r.tenants[:1]
    ref = refcheck.training_reference(cell, t, r.backbone, r.adapters0[:1],
                                      r.readings["layout"], SEED, 1, r.adam)
    prog = {"losses": [r.readings["losses"][0][:1]],
            "grads": r.readings["grads"][:1],
            "change": ref["change"]}
    nums = check.training_numbers(prog, ref)
    return {k: nums[k] for k in ("loss_gap", "grad_gap")}


def test_first_step_matches_with_every_mechanism(first_step):
    gaps = first_step_gaps(first_step, first_step.cell.cfg)
    assert all(gaps[k] <= first_step.cell.limits[k] for k in gaps), gaps


@pytest.mark.parametrize("mechanism", [
    "conv_bias", "nope", "attention_multiplier", "residual_multiplier",
    "embedding_multiplier", "logits_scaling"])
def test_a_reference_without_the_mechanism_fails(first_step, mechanism):
    """The program runs each mechanism: against a reference that leaves it
    out, its first step is past a limit of the cell."""
    gaps = first_step_gaps(first_step, without(first_step.cell.cfg, mechanism))
    lim = first_step.cell.limits
    assert any(gaps[k] > lim[k] for k in gaps), (mechanism, gaps)
