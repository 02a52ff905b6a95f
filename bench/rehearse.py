"""Compile a cell's programs for a described TPU v5e, without the chip, and
print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

Builds the cell's service from shapes alone (no weight, adapter or
optimizer array is made here), then compiles, on the compiled ``pallas``
kernel tier, every hTask's training step for one chip of a described
``v5e:2x2``, and prints ``memory_analysis()`` of each.  What the chip's compiler refuses (a kernel
tile, fast memory, a program that does not fit) it refuses here, at no
chip time.  Run it before a new cell's first call to the chip.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def _abstract_state():
    """Make the program build its adapter stacks and optimizer moments as
    shapes only (this rehearsal never holds a full-size array)."""
    import jax

    from repro.core import registry
    from repro.peft.multitask import MultiTaskAdapters
    from repro.train import optimizer

    MultiTaskAdapters.init = lambda self, key: self.abstract()
    registry.adamw_init = lambda params: jax.eval_shape(optimizer.adamw_init, params)
    registry.ModelGenerator._migrate = (
        lambda self, old, mta, params, opt, tasks: (params, opt))


def _on(sharding, tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _report(label: str, compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{label}: arguments {m.argument_size_in_bytes / 2**30:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / 2**30:.3f} GiB, aliased "
          f"{m.alias_size_in_bytes / 2**30:.3f} GiB, temporaries "
          f"{m.temp_size_in_bytes / 2**30:.3f} GiB; total {total / 2**30:.3f} GiB",
          flush=True)
    return total


def rehearse(name: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness as hz
    import traffic as tr
    from repro.core import ParallelismSpec
    from repro.kernels import ops as kops
    from repro.serve import AdmissionConfig, MuxTuneService, TenantSpec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    _abstract_state()
    kops.set_impl("pallas")

    cell = hz.find_cell(name)
    ac = hz.arch_config(cell.cfg)
    svc = MuxTuneService(ac, ParallelismSpec(), lr=float(cell.traffic["lr"]),
                         admission=AdmissionConfig(**cell.traffic["service"]))
    svc.gen.backbone_params = svc.gen.model.abstract_params()
    for t in tr.tenants(cell.traffic, cell.cfg):
        rec = svc.submit(TenantSpec(hz.program_task(t), target_steps=10**9))
        if rec.state != "running":
            raise RuntimeError(f"tenant {t.tenant_id} {rec.state}: {rec.reason}")
    eng = svc.engine
    bb = _on(chip, eng.backbone)
    ad = _on(chip, eng.reg.adapter_params)
    opt = _on(chip, eng.reg.opt_state)
    slots = _on(chip, eng._slot_steps)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(bb))
    state = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((ad, opt)))
    print(f"{name}: backbone {weights / 2**30:.3f} GiB, adapters and moments "
          f"{state / 2**30:.3f} GiB", flush=True)
    n_acc = max(len(eng.plan.tasks), sum(eng.reg.mta.kind_capacity.values()))
    acc = _on(chip, (jax.ShapeDtypeStruct((), np.float32),
                     jax.ShapeDtypeStruct((n_acc,), np.float32)))
    worst = 0
    for h, ht in enumerate(eng.plan.htasks):
        batch = _on(chip, jax.eval_shape(lambda b: b, next(svc._loaders[h])))
        members = _on(chip, eng._member_ids[h])
        step = eng._make_step(h)
        compiled = step.lower(bb, ad, opt, slots, batch, members, acc).compile()
        worst = max(worst, _report(f"hTask {h} step ({ht.rows} x {ht.row_len})",
                                   compiled))
    print(f"{name}: the largest program needs {worst / 2**30:.3f} GiB of the "
          "chip's 16 GB (each counts its own arguments)", flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    return rehearse(ap.parse_args().workload)


if __name__ == "__main__":
    sys.exit(main())
