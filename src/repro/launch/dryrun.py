import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces three compiles:
  * ``full``   — production config (scan-over-layers, remat):
                 ``.lower().compile()`` MUST succeed; provides
                 ``memory_analysis()`` (per-device bytes) and the collective
                 schedule sanity check.
  * ``cost@a`` / ``cost@b`` — small-L UNROLLED twins (layers and inner flash
                 /GLA scans unrolled) whose ``cost_analysis()`` and parsed
                 collective bytes extrapolate linearly (C(L) = F + L*P) to
                 the full depth — XLA's HloCostAnalysis visits while bodies
                 once, so scanned compiles cannot be costed directly.

Artifacts: one JSON per cell under ``artifacts/dryrun/`` consumed by
``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, SHAPES, ArchConfig, ShapeSpec, dryrun_cells, get_config
from repro.distributed.sharding import activate_rules
from repro.launch.mesh import make_production_mesh
from repro.launch.rules import attn_mode_for, dp_size, rules_for, tp_size
from repro.launch import steps as S
from repro.models.flags import cost_unroll_scans
from repro.models.transformer import Model
from repro.train.optimizer import adamw_init

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype, 4)
    if not dims:
        return b
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n * b


def _group_size(line: str, default: int = 16) -> int:
    """Participant count from replica_groups (iota or explicit format)."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,\s]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Collective accounting from (post-SPMD) HLO text.

    Two metrics per op class:
      * ``bytes``      — operand-size sum (the mandated §Roofline metric);
      * ``wire_bytes`` — per-device link traffic under ring semantics:
          all-reduce      2*(P-1)/P * operand
          all-gather      (P-1)/P   * result (gathered size)
          reduce-scatter  (P-1)/P   * operand
          all-to-all      (P-1)/P   * operand
          collective-permute          operand
    """
    defre = re.compile(r"%?([\w\.\-]+)\s*=\s*\(?([a-z0-9]+)\[([0-9,\s]*)\]")
    sizes: Dict[str, int] = {}
    for m in defre.finditer(hlo):
        sizes[m.group(1)] = _shape_bytes(m.group(2), m.group(3))
    out = {c: {"count": 0, "bytes": 0, "wire_bytes": 0} for c in COLLECTIVES}
    for line in hlo.splitlines():
        for c in COLLECTIVES:
            if f" {c}(" in line or f"={c}(" in line or f" {c}-start(" in line:
                m = re.search(r"=\s*\(?([a-z0-9]+)\[([0-9,\s]*)\]", line)
                result_b = _shape_bytes(m.group(1), m.group(2)) if m else 0
                ops = re.findall(r"[\(,]\s*%?([\w\.\-]+)", line.split("(", 1)[1]) if "(" in line else []
                b = sum(sizes[o] for o in ops if o in sizes)
                if b == 0:
                    b = result_b
                P = _group_size(line)
                ring = (P - 1) / max(P, 1)
                if c == "all-reduce":
                    wire = 2.0 * ring * b
                elif c == "all-gather":
                    wire = ring * max(result_b, b)
                elif c == "reduce-scatter":
                    wire = ring * b
                elif c == "all-to-all":
                    wire = ring * b
                else:  # collective-permute
                    wire = float(b)
                out[c]["count"] += 1
                out[c]["bytes"] += b
                out[c]["wire_bytes"] += int(wire)
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_wire_bytes"] = sum(v["wire_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


def _units(cfg: ArchConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_periods
    if cfg.family == "ssm":
        return cfg.num_layers // cfg.slstm_period
    return cfg.num_layers


def _with_units(cfg: ArchConfig, units: int, scan: bool, cost_blocks: Optional[int]) -> ArchConfig:
    if cfg.family == "hybrid":
        L = units * len(cfg.layer_pattern)
    elif cfg.family == "ssm":
        L = units * cfg.slstm_period
    else:
        L = units
    over: Dict[str, Any] = {"num_layers": L, "scan_layers": scan}
    if cfg.family == "audio":
        over["num_encoder_layers"] = L
    if cost_blocks:
        over["attn_q_block"] = cost_blocks
        over["attn_kv_block"] = cost_blocks
    return cfg.with_overrides(**over)


def _build(cfg: ArchConfig, shape: ShapeSpec, mesh, overrides=None):
    overrides = dict(overrides or {})
    attn_impl = overrides.pop("attn_impl", None)
    rules = rules_for(cfg, shape, mesh, overrides or None)
    model = Model(cfg, attn_mode=attn_impl or attn_mode_for(cfg, mesh))
    return model, rules


def lower_cell(
    cfg: ArchConfig,
    shape: ShapeSpec,
    mesh,
    kind: str,
    overrides=None,
    n_tasks: int = 8,
) -> Tuple[Any, Any]:
    """Lower one cell; returns (lowered, meta)."""
    model, rules = _build(cfg, shape, mesh, overrides)
    pshard = S.param_shardings(model, mesh, rules)
    pspecs = model.abstract_params()

    role = kind
    with_pos = model.attn_mode == "striped_cp"
    with activate_rules(mesh, rules):
        if role == "train":
            mta, seg = S.dryrun_tasks(cfg, shape, n_tasks=n_tasks)
            ad_specs = mta.abstract()
            ad_shard = S.adapter_shardings(mta, mesh, rules)
            opt_specs = jax.eval_shape(adamw_init, ad_specs)
            opt_shard = S.opt_shardings(opt_specs, mesh, mta=mta, cfg=cfg,
                                        rules=rules)
            bspecs = S.batch_specs(cfg, shape, with_positions=with_pos)
            bshard = S.batch_shardings(bspecs, mesh, rules)
            step = S.build_train_step(model, mta, seg)
            fn = jax.jit(step, in_shardings=(pshard, ad_shard, opt_shard, bshard),
                         donate_argnums=(1, 2))
            lowered = fn.lower(pspecs, ad_specs, opt_specs, bspecs)
        elif role == "prefill":
            bspecs = S.batch_specs(cfg, shape, with_labels=False, with_positions=with_pos)
            bshard = S.batch_shardings(bspecs, mesh, rules)
            step = S.build_prefill_step(model)
            fn = jax.jit(step, in_shardings=(pshard, bshard))
            lowered = fn.lower(pspecs, bspecs)
        else:  # decode
            st_specs = S.decode_state_specs(model, shape)
            st_shard = S.decode_state_shardings(model, shape, mesh, rules)
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            tok_shard = S.batch_shardings({"tokens": tok}, mesh, rules)["tokens"]
            step = S.build_serve_step(model)
            fn = jax.jit(step, in_shardings=(pshard, st_shard, tok_shard),
                         donate_argnums=(1,))
            lowered = fn.lower(pspecs, st_specs, tok)
    return lowered, {"attn_mode": model.attn_mode}


def _mem_dict(ma) -> Dict[str, float]:
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
        "total_bytes": ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    overrides=None,
    skip_full: bool = False,
    cost_units: Tuple[int, int] = (1, 2),
    n_tasks: int = 8,
    tag: str = "",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shape.kind
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
        "kind": kind, "chips": int(np.prod(list(mesh.shape.values()))),
        "tp": tp_size(mesh), "dp": dp_size(mesh), "tag": tag,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
    }

    # ---- cost twins (small-L, unrolled) -----------------------------------
    a, b = cost_units
    cost_blocks = max(shape.seq_len // 8, 512) if kind != "decode" else None
    costs = {}
    for u in (a, b):
        cfg_u = _with_units(cfg, u, scan=False, cost_blocks=cost_blocks)
        t0 = time.time()
        with cost_unroll_scans(True):
            lowered, meta = lower_cell(cfg_u, shape, mesh, kind, overrides, n_tasks)
            compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = parse_collectives(compiled.as_text())
        costs[u] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": coll,
            "compile_s": time.time() - t0,
        }
        result["attn_mode"] = meta["attn_mode"]
    # linear extrapolation to full depth
    U = _units(cfg)
    def extrap(fa: float, fb: float) -> float:
        p = (fb - fa) / (b - a)
        f = fa - a * p
        return f + U * p
    result["cost"] = {
        "per_device_flops": extrap(costs[a]["flops"], costs[b]["flops"]),
        "per_device_bytes": extrap(costs[a]["bytes"], costs[b]["bytes"]),
        "per_device_collective_bytes": extrap(
            costs[a]["collectives"]["total_bytes"], costs[b]["collectives"]["total_bytes"]),
        "per_device_collective_wire_bytes": extrap(
            costs[a]["collectives"].get("total_wire_bytes", 0),
            costs[b]["collectives"].get("total_wire_bytes", 0)),
        "collective_detail_at_b": costs[b]["collectives"],
        "units_full": U, "units_measured": [a, b],
        "raw": {str(k): {kk: vv for kk, vv in v.items() if kk != "collectives"}
                for k, v in costs.items()},
    }

    # ---- full production compile ------------------------------------------
    if not skip_full:
        t0 = time.time()
        lowered, meta = lower_cell(cfg, shape, mesh, kind, overrides, n_tasks)
        compiled = lowered.compile()
        result["full"] = {
            "memory": _mem_dict(compiled.memory_analysis()),
            "compile_s": time.time() - t0,
        }
        del compiled, lowered
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-full", action="store_true")
    ap.add_argument("--n-tasks", type=int, default=8)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--override", action="append", default=[],
                    help="rule override logical=mesh_axis (e.g. seq=none)")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=")
        overrides[k] = None if v.lower() in ("none", "null") else (
            tuple(v.split("+")) if "+" in v else v)

    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shp in dryrun_cells(arch):
                cells.append((arch, shp))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    n_ok = n_fail = 0
    for arch, shp in cells:
        for mp in meshes:
            name = f"{arch}__{shp}__{'multi' if mp else 'single'}"
            if args.tag:
                name += f"__{args.tag}"
            path = os.path.join(args.out, name + ".json")
            print(f"=== {name} ===", flush=True)
            t0 = time.time()
            try:
                res = run_cell(arch, shp, mp, overrides or None,
                               skip_full=args.skip_full, n_tasks=args.n_tasks,
                               tag=args.tag)
                res["ok"] = True
                n_ok += 1
            except Exception as e:
                res = {"arch": arch, "shape": shp,
                       "mesh": "multi" if mp else "single", "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-3000:]}
                n_fail += 1
                print(f"FAILED: {res['error']}", flush=True)
            res["wall_s"] = time.time() - t0
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            if res.get("ok"):
                mem = res.get("full", {}).get("memory", {})
                print(
                    f"ok  flops/dev={res['cost']['per_device_flops']:.3e} "
                    f"coll/dev={res['cost']['per_device_collective_bytes']:.3e}B "
                    f"mem/dev={mem.get('total_bytes', 0)/2**30:.2f}GiB "
                    f"wall={res['wall_s']:.0f}s", flush=True)
    print(f"\ndone: {n_ok} ok, {n_fail} failed")


if __name__ == "__main__":
    main()
