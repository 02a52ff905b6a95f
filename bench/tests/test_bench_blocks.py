"""Block families found by name (``bench/blocks/<family>.py``): the dense
family gives the numbers that the benchmark gave before its block code moved
there, a family enters by new files alone, and a configuration whose family
has no module is refused, naming the files looked for."""
import hashlib
import json
import os
import sys
import time

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import blocks  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic as tr  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

FIXTURE = harness.Path(HERE) / "fixtures" / "root"
SEED = 2**31 + 17
PREFIX_ROWS = 4       # tiny-train's prefix tenant's rank: its stack's width

# sha256 of the fixture cell tiny.train's weights at SEED (``digest``),
# recorded at commit eca53b7, before the dense block moved into
# bench/blocks/dense.py
PINNED = {
    "backbone": "b2a41e9c14795e0b59e9a921e29d5fff1e1565ed5f44877900886b2e52bce280",
    "adapters": "407e85c836c347ccbf4e66588edf93b1f2e099c9e423143aa8a3b6e76638107a",
}


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes, in path order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def weights_of(cell, what: str):
    if what == "backbone":
        return weights.make_backbone(cell.cfg, SEED)
    ts = tr.tenants(cell.traffic, cell.cfg)
    return weights.make_adapters(cell.cfg, ts, SEED, PREFIX_ROWS)


def test_smollm_work_counts_are_pinned():
    # the counts of the tree before the block families, which mfu.train reads
    cell = harness.find_cell("smollm-360m.train-solo")
    ts = tr.tenants(cell.traffic, cell.cfg)
    assert work.train_flops(cell.cfg, ts) == 27125738373120.0
    assert work.matmul_params(cell.cfg) == 361758720


@pytest.mark.parametrize("what", sorted(PINNED))
def test_dense_weights_are_pinned(what):
    cell = harness.find_cell("tiny.train", FIXTURE)
    assert digest(weights_of(cell, what)) == PINNED[what]


def one_loss(cell):
    """The reference's loss of the first tenant on one short sequence."""
    t = tr.tenants(cell.traffic, cell.cfg)[0]
    ad = weights_of(cell, "adapters")[0]
    bb = weights.make_backbone(cell.cfg, SEED)
    batch = reference.sequences_batch(
        [{"tokens": list(range(1, 33)), "last_label_zero": False}])
    ref = reference.Reference(cell.cfg)
    return float(ref.tenant_loss(ad, bb, batch, t.method, t.scale))


def test_a_family_enters_by_new_files_alone():
    """``dense_alias`` lives only under the fixture root (a module and a
    configuration that names it); the generic modules reach it by name and
    give what the dense family gives at the same sizes."""
    alias = harness.find_cell("tiny-alias.train", FIXTURE)
    tiny = harness.find_cell("tiny.train", FIXTURE)
    fam = blocks.family(alias.cfg)
    assert harness.Path(fam.__file__) == (
        FIXTURE / "bench" / "blocks" / "dense_alias.py").resolve()
    assert not (harness.ROOT / "bench" / "blocks" / "dense_alias.py").exists()
    assert fam is not blocks.family(tiny.cfg)

    assert harness.arch_config(alias.cfg) == harness.arch_config(tiny.cfg)
    ts = tr.tenants(alias.traffic, alias.cfg)
    assert ts == tr.tenants(tiny.traffic, tiny.cfg)
    for what in PINNED:
        assert digest(weights_of(alias, what)) == PINNED[what]
    assert work.train_flops(alias.cfg, ts) == work.train_flops(tiny.cfg, ts) > 0
    assert one_loss(alias) == one_loss(tiny)


def test_a_new_family_runs_through_set_up_and_the_check():
    res = run.execute(FIXTURE, "tiny-alias.train", SEED, 1.0, False,
                      t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


ENTRIES = {
    "arch_config": lambda cell: harness.arch_config(cell.cfg),
    "tenants": lambda cell: tr.tenants(cell.traffic, cell.cfg),
    "make_backbone": lambda cell: weights.make_backbone(cell.cfg, SEED),
    "Reference": lambda cell: reference.Reference(cell.cfg),
    "train_flops": lambda cell: work.train_flops(cell.cfg, []),
}


def with_family(name):
    cell = harness.find_cell("tiny.train", FIXTURE)
    cell.cfg = json.loads(json.dumps(cell.cfg))
    if name is None:
        del cell.cfg["block"]["family"]
    else:
        cell.cfg["block"]["family"] = name
    return cell


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_missing_family_is_refused(entry):
    cell = with_family("no_such_block")
    with pytest.raises(FileNotFoundError) as e:
        ENTRIES[entry](cell)
    msg = str(e.value)
    assert str(FIXTURE / "bench" / "blocks" / "no_such_block.py") in msg
    assert str(harness.ROOT / "bench" / "blocks" / "no_such_block.py") in msg


def test_a_configuration_must_name_its_family():
    with pytest.raises(KeyError, match="block.family"):
        harness.arch_config(with_family(None).cfg)
