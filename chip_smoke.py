"""Chip smoke test: MuxTune's main path, once, on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # fleet placement across four chips

One chip: smollm-360m at its published width (``get_config`` with no
overrides, random weights from a seed) serves four PEFT tenants (two LoRA,
one adapter-tuning, one prefix-tuning) through ``MuxTuneService`` on the
compiled ``pallas`` kernel tier, with three seeded decode requests
co-served between training micro-steps.  The same tenants are then
retrained for the first steps on the ``xla`` tier in this process, and
each tenant's per-step loss must agree within a bf16 tolerance.

``--chips 4``: the fleet router places four instances, one per chip, and
the same run with every instance on chip 0 is the reference: per-tenant
losses must match, each instance's adapters must live on its own chip, and
the warm steps must run with device-to-device transfers disallowed.

Per-step seconds printed here are smoke timing (compilation included in
the first step), not a benchmark.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; any failed check raises, and
the script exits non-zero without printing it.  It refuses to run where
JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-360m"
TENANTS = "sst2:lora:16,qa:lora:8,rte:adapter:8,sst2:prefix:16"
MICRO_BATCH = 16
TENANT_LR = 2e-3
STEPS = 5
COMPARE_STEPS = 3
# bf16 activations on both tiers; the kernels differ in summation order
LOSS_RTOL = 2e-2
REQUEST_SEEDS = (11, 12, 13)
NEW_TOKENS = 8
FLEET_INSTANCES = 4
FLEET_LAYERS = 2      # fleet check: published width, depth cut to 2 layers
FLEET_STEPS = 4
FLEET_RTOL = 1e-5


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def build_service(cfg, impl: str, coserve=None):
    """Four tenants on one ``MuxTuneService`` under kernel tier ``impl``."""
    from repro.core import ParallelismSpec
    from repro.kernels import ops as kops
    from repro.launch.train import parse_tasks
    from repro.serve import MuxTuneService, TenantSpec

    kops.set_impl(impl)
    tasks = [dataclasses.replace(
        t, adapter=dataclasses.replace(t.adapter, lr=TENANT_LR))
        for t in parse_tasks(TENANTS, MICRO_BATCH)]
    svc = MuxTuneService(cfg, ParallelismSpec(), lr=TENANT_LR, seed=0,
                         coserve=coserve)
    for t in tasks:
        rec = svc.submit(TenantSpec(t, target_steps=10 * STEPS))
        check(rec.state == "running", f"{t.task_id} not admitted: {rec.reason}")
    return svc, [t.task_id for t in tasks]


def custom_calls_in_step(svc) -> int:
    """``tpu_custom_call`` ops in hTask 0's compiled training step."""
    import jax

    eng = svc.engine
    step = eng._steps[eng.step_signature(0)]
    batch = next(svc._loaders[0])
    n_acc = max(len(eng.plan.tasks), sum(eng.reg.mta.kind_capacity.values()))
    acc = (jax.ShapeDtypeStruct((), np.float32),
           jax.ShapeDtypeStruct((n_acc,), np.float32))
    text = step.lower(eng.backbone, eng.reg.adapter_params, eng.reg.opt_state,
                      eng._slot_steps, batch, eng._member_ids[0],
                      acc).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def train(svc, ids, steps: int, label: str):
    """``steps`` service iterations; per-tenant losses per step."""
    losses = {tid: [] for tid in ids}
    for i in range(steps):
        m = svc.step()
        check(m is not None, f"{label}: step {i} trained nothing")
        for tid in ids:
            losses[tid].append(svc.tenants[tid].losses[-1])
        print(f"[{label}] step {i}: losses "
              + " ".join(f"{tid}={losses[tid][-1]:.4f}" for tid in ids)
              + f" | smoke wall {m.wall_seconds:.3f}s"
              f" | decode tokens {m.decode_tokens}", flush=True)
    return losses


def one_chip(cfg) -> None:
    import jax

    from repro.serve import CoServeConfig, RequestSpec

    # a generous SLO and one pool row per request, so every request
    # completes within the smoke's few iterations
    coserve = CoServeConfig(decode_slots=len(REQUEST_SEEDS), slo_seconds=30.0,
                            max_new_cap=16, decode_max_len=64)
    svc, ids = build_service(cfg, "pallas", coserve)
    check(svc.engine.step_signature(0)[0] == "pallas",
          f"engine step tier {svc.engine.step_signature(0)[0]!r}")
    rng = np.random.RandomState(0)
    rids = []
    for tid, seed in zip((ids[0], ids[2], ids[3]), REQUEST_SEEDS):
        prompt = rng.randint(1, cfg.vocab_size, size=12)
        req = svc.submit_request(tid, RequestSpec(
            prompt, max_new_tokens=NEW_TOKENS, temperature=0.7, top_k=50,
            seed=seed))
        rids.append(req.request_id)
    pallas = train(svc, ids, STEPS, "pallas")

    for tid, ls in pallas.items():
        check(all(np.isfinite(ls)), f"{tid}: non-finite loss {ls}")
    for tid in ids:
        if svc.tenants[tid].task.adapter.kind == "lora":
            check(pallas[tid][-1] < pallas[tid][0],
                  f"{tid}: LoRA loss did not fall {pallas[tid]}")
    for rid in rids:
        req = svc.coserve.requests[rid]
        n = 0 if req.tokens_out is None else len(req.tokens_out)
        print(f"request {rid}: {req.state}, {n} tokens", flush=True)
        check(req.state == "done" and n == NEW_TOKENS,
              f"request {rid} ended {req.state} with {n} tokens")
    n_calls = custom_calls_in_step(svc)
    print(f"tpu_custom_call ops in one compiled training step: {n_calls}",
          flush=True)
    check(n_calls > 0, "compiled training step runs no Pallas kernel")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}", flush=True)

    del svc
    gc.collect()
    xsvc, _ = build_service(cfg, "xla")
    xla = train(xsvc, ids, COMPARE_STEPS, "xla")
    for tid in ids:
        got = np.asarray(pallas[tid][:COMPARE_STEPS])
        want = np.asarray(xla[tid])
        rel = float(np.max(np.abs(got - want) / np.abs(want)))
        print(f"{tid}: pallas vs xla max rel diff {rel:.2e}", flush=True)
        check(rel <= LOSS_RTOL,
              f"{tid}: pallas {got} vs xla {want} beyond rtol {LOSS_RTOL}")


def fleet_run(cfg, devices):
    """The replay's tenants through a ``FleetRouter`` of four instances
    placed on ``devices``: per-tenant losses and each instance's device."""
    import jax

    from repro.core import ParallelismSpec
    from repro.fleet import FleetRouter
    from repro.serve import AdmissionConfig, MuxTuneService, RequestSpec
    from repro.serve import TenantSpec
    from repro.serve.replay import arrival_to_task, tiny_trace

    def factory(iid):
        # one tenant per instance, so every chip trains one
        return MuxTuneService(cfg, ParallelismSpec(), seed=0, reserve_slots=1,
                              enable_fusion=False,
                              admission=AdmissionConfig(max_tenants=1))

    fleet = FleetRouter(factory, n_instances=FLEET_INSTANCES, policy="fcfs",
                        devices=devices)
    trace = tiny_trace(FLEET_INSTANCES)
    ids = []
    for i, arr in enumerate(trace):
        task = arrival_to_task(arr, i)
        ids.append(task.task_id)
        fleet.submit(TenantSpec(task, target_steps=FLEET_STEPS + 1))
        fleet.submit_request(task.task_id, RequestSpec(
            np.arange(1, 9) + i, max_new_tokens=4, seed=i))
    fleet.step()  # warm-up: every instance compiles its steps
    homes = {}
    for iid, inst in fleet.instances.items():
        leaves = jax.tree.leaves(inst.service.engine.reg.adapter_params)
        homes[iid] = {d for leaf in leaves for d in leaf.devices()}
    with jax.transfer_guard_device_to_device("disallow"):
        for _ in range(FLEET_STEPS - 1):
            fleet.step()
    losses = {tid: list(fleet.record(tid).losses) for tid in ids}
    return losses, homes


def four_chips() -> None:
    import jax

    from repro.configs import get_config

    devices = jax.devices()
    check(len(devices) == FLEET_INSTANCES,
          f"--chips 4 needs {FLEET_INSTANCES} chips, found {len(devices)}")
    cfg = get_config(ARCH).with_overrides(num_layers=FLEET_LAYERS)
    spread, homes = fleet_run(cfg, devices)
    for iid, devs in sorted(homes.items()):
        print(f"instance {iid}: adapters on {sorted(str(d) for d in devs)}",
              flush=True)
        check(devs == {devices[iid]}, f"instance {iid} adapters on {devs}")
    stacked, homes0 = fleet_run(cfg, [devices[0]])
    check(all(d == {devices[0]} for d in homes0.values()),
          f"chip-0 placement leaked: {homes0}")
    for tid in spread:
        print(f"{tid}: spread {spread[tid]} | chip 0 {stacked[tid]}",
              flush=True)
        check(len(spread[tid]) == FLEET_STEPS,
              f"{tid}: trained {len(spread[tid])} steps")
        np.testing.assert_allclose(spread[tid], stacked[tid], rtol=FLEET_RTOL,
                                   err_msg=f"{tid}: placement changed losses")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"platform: {dev.platform}", flush=True)
    print(f"device_kind: {dev.device_kind}", flush=True)
    print(f"device_count: {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("no TPU found: refusing to run", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile_cache: {configure_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip(get_config(ARCH))
    print(f"smoke seconds (compilation included): "
          f"{time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
