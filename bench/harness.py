"""What a run is made of, found by name: the cell in ``BENCHMARK.json``, its
configuration and traffic files, its limits and its per-layer metric
readers; and the drive of the program under test (set-up, the measured
window, the check phase).

A later cell, configuration, traffic mix or metric is added by new files
and entries alone:

* ``bench/configs/<config>.json``  sizes, source, cuts, the block family,
  and the program's architecture id with the overrides that make it run
  those sizes;
* ``bench/blocks/<family>.py``     what depends on the block: the program
  config check, sizes and sites, weights, adapter layout, the reference's
  block and the work counts (``bench/blocks/__init__.py``);
* ``bench/traffic/<traffic>.json`` tenants, their frozen lengths, the
  service settings;
* ``bench/limits/<cell>.json``     the limit of every number compared;
* ``bench/metrics/<metric>.py``    a reader ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

import blocks
import traffic as tr

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    cfg: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = confs[w["config"]]
    bench = root / "bench"
    cfg = load_json(root / conf["file"])
    cfg[blocks.DIR_KEY] = str(bench / "blocks")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], cfg=cfg,
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the program's configuration


def arch_config(cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file, checked by its
    block family against the file's sizes so that the program runs what the
    file says."""
    return blocks.family(cfg).program_config(cfg)


def program_task(t: tr.Tenant):
    from repro.core.task import PEFTTask
    from repro.peft.methods import AdapterConfig

    return PEFTTask(t.tenant_id, AdapterConfig(t.method, rank=t.rank,
                                               alpha=t.alpha, targets=t.targets,
                                               lr=t.lr),
                    seq_lengths=t.lengths, micro_batch=t.micro_batch,
                    pad_len=t.pad_len)


# ---------------------------------------------------------------------------
# set-up


@dataclass
class Run:
    """One run's service, tenants and what the check needs from set-up."""

    cell: Cell
    seed: int
    svc: Any
    tenants: List[tr.Tenant]
    backbone: Dict
    adapters0: List[Dict]       # the seed's initial adapters (bench layout)
    prefix_rows: int
    adam: Dict[str, float]      # the program's AdamW hyperparameters
    readings: Dict = field(default_factory=dict)
    check_s: float = 0.0        # host seconds spent taking check readings


def program_adam() -> Dict[str, float]:
    """The AdamW hyperparameters the program's optimizer states: the
    defaults of ``adamw_update``, which the engine does not override.  The
    reference trains with them, and the first gradient is read back from
    the first moment with this ``b1``."""
    import inspect

    from repro.train.optimizer import adamw_update

    params = inspect.signature(adamw_update).parameters
    hp = {k: float(params[k].default) for k in ("b1", "b2", "eps", "weight_decay")}
    if hp.pop("weight_decay"):
        raise RuntimeError("the program's AdamW decays weights; the reference "
                           "does not")
    return hp


def build(cell: Cell, seed: int) -> Run:
    """Weights from the seed, the service, every tenant admitted, the
    seed's adapters installed.  Raises when a tenant is not admitted."""
    import jax
    from repro.core import ParallelismSpec
    from repro.serve import AdmissionConfig, MuxTuneService, TenantSpec

    import weights as wt

    ac = arch_config(cell.cfg)
    tenants = tr.tenants(cell.traffic, cell.cfg)
    bb = wt.make_backbone(cell.cfg, seed)
    svc = MuxTuneService(
        ac, ParallelismSpec(), lr=float(cell.traffic["lr"]), seed=seed,
        admission=AdmissionConfig(**cell.traffic["service"]))
    prog_bb = wt.program_backbone(cell.cfg, bb)
    wt.same_layout(prog_bb, svc.gen.model.abstract_params(), "backbone")
    svc.gen.backbone_params = prog_bb
    for t in tenants:
        rec = svc.submit(TenantSpec(program_task(t), target_steps=10**9))
        if rec.state != "running":
            raise RuntimeError(f"tenant {t.tenant_id} {rec.state}: {rec.reason}")
    kind_rank = svc.engine.reg.mta.kind_rank
    prefix_rows = int(kind_rank.get("prefix", 0))
    adapters = wt.make_adapters(cell.cfg, tenants, seed, prefix_rows)
    wt.install_adapters(cell.cfg, svc, tenants, adapters)
    # the reference's copy lives on the host: the chip's memory is the
    # program's
    adapters = jax.device_get(adapters)
    return Run(cell, seed, svc, tenants, bb, adapters, prefix_rows, program_adam())


def tenant_slices(run: Run, tree) -> List[Dict]:
    """Each tenant's own leaves (its slot, its rank) of a program tree laid
    out like the adapter stacks, as host float32 arrays."""
    import jax

    import weights as wt

    cfg = run.cell.cfg
    fam = blocks.family(cfg)
    slots = wt.tenant_slots(run.svc, run.tenants)
    shapes = [wt.adapter_shapes(cfg, t, run.prefix_rows) for t in run.tenants]
    kinds = [t.method for t in run.tenants]

    def take(tree):
        stacks = fam.from_program(cfg, tree)      # [L, cap, ...] per site
        out = []
        for kind, slot, shp in zip(kinds, slots, shapes):
            out.append({site: {leaf: stacks[kind][site][leaf][:, slot][
                (slice(None),) + tuple(slice(0, s) for s in leaves[leaf][1:])]
                .astype(np.float32) for leaf in leaves}
                for site, leaves in shp.items()})
        return out

    return jax.device_get(jax.jit(take)(tree))


def train_readings(run: Run, steps: int = 3) -> None:
    """Drive the service through its first ``steps`` steps and keep what
    the training check compares: each tenant's losses, its first gradient
    (from the optimizer's first moment after one step) and its parameters
    after the last of them."""
    svc = run.svc
    for i in range(steps):
        svc.step()
        t0 = time.perf_counter()
        if i == 0:
            m = tenant_slices(run, svc.engine.reg.opt_state.m)
            keep = 1.0 - run.adam["b1"]
            run.readings["grads"] = [
                {s: {k: v / keep for k, v in leaves.items()} for s, leaves in t.items()}
                for t in m]
        if i == steps - 1:
            run.readings["params"] = tenant_slices(run, svc.engine.reg.adapter_params)
        run.check_s += time.perf_counter() - t0
    run.readings["losses"] = [list(svc.tenants[t.tenant_id].losses[:steps])
                              for t in run.tenants]
    run.readings["layout"] = training_layout(run)


def training_layout(run: Run) -> Dict[str, List]:
    """Each tenant's sequences in the order the program draws their tokens
    (rows of its hTask in order, segments in order): (length, padded)."""
    svc = run.svc
    plan = svc.plan
    ids = [t.task_id for t in plan.tasks]
    out = {t.tenant_id: [] for t in run.tenants}
    for ap in plan.alignment:
        for row in ap.rows:
            tid = ids[row.task]
            out[tid].extend((s.length, s.padded) for s in row.segments)
    for t in run.tenants:
        got = sorted(l for l, _ in out[t.tenant_id])
        if got != sorted(t.lengths):
            raise RuntimeError(f"{t.tenant_id}: the program trains lengths "
                               f"{got}, the traffic file says {sorted(t.lengths)}")
    return out


# ---------------------------------------------------------------------------
# the measured window


@dataclass
class Window:
    seconds: float = 0.0
    steps: List[Any] = field(default_factory=list)         # StepMetrics
    step_times: List[tuple] = field(default_factory=list)  # (start, end)


def run_window(run: Run, seconds: float, trace_hook=None) -> Window:
    """Steps back to back until ``seconds`` have passed, each whole.
    ``trace_hook(i, when)`` is called before (``when=0``) and after
    (``when=1``) step ``i``."""
    svc = run.svc
    win = Window()
    t0 = time.perf_counter()
    i = 0
    while True:
        if trace_hook:
            trace_hook(i, 0)
        ts = time.perf_counter() - t0
        m = svc.step()
        te = time.perf_counter() - t0
        if trace_hook:
            trace_hook(i, 1)
        win.steps.append(m)
        win.step_times.append((ts, te))
        i += 1
        if te >= seconds:
            break
    win.seconds = win.step_times[-1][1]
    return win


def free_program(run: Run) -> None:
    """Drop the program's state so the reference runs in the memory it
    held; the seed's backbone (the benchmark's own arrays) stays."""
    run.svc = None
    gc.collect()
