"""§Roofline: three-term roofline per (arch x shape x mesh) from dry-run
artifacts.

  compute   = HLO_FLOPs/dev / peak            (197 TFLOP/s bf16 per chip)
  memory    = HLO_bytes/dev / HBM_bw          (819 GB/s)
  collective= collective_bytes/dev / link_bw  (~50 GB/s/link ICI)

All three use per-device quantities from the SPMD-partitioned module (the
global formulation divided by `chips` is identical).  HLO FLOPs/bytes come
from the small-L unrolled twins' linear extrapolation (dryrun.py); sLSTM's
time recurrence stays scanned and is corrected analytically here.  MODEL
FLOPs = 6·N·D train / 2·N·tokens decode (active N for MoE) — both the
mandated 6ND ratio and the PEFT-corrected ~4ND ratio are reported
(DESIGN.md §8).

CPU-backend caveat (documented in EXPERIMENTS.md): memory_analysis inflates
temps with f32 operand copies of bf16 weights (no native bf16 dots on CPU);
an analytic per-device memory model provides the HBM-fit verdict, with the
measured number kept as the upper bound.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from repro.configs import SHAPES, get_config
from repro.core.cost_model import V5E

# the dry-run's target chip: a v5e (published peaks, one table)
PEAK = V5E.peak_flops
HBM_BW = V5E.hbm_bw
ICI_BW = V5E.ici_bw
HBM = V5E.hbm_bytes

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")
OUT = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def _slstm_correction_flops(cfg, shape, chips: int, train: bool) -> float:
    """sLSTM recurrence FLOPs hidden inside a (non-unrolled) time scan."""
    if cfg.family != "ssm" or not cfg.slstm_period:
        return 0.0
    n_slstm = cfg.num_layers // cfg.slstm_period
    nh, hd = cfg.num_heads, cfg.d_model // cfg.num_heads
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    per_tok = 2.0 * nh * hd * 4 * hd  # recurrent matmul
    mult = 3.0 if (train and shape.kind == "train") else 1.0
    return n_slstm * tokens * per_tok * mult / chips


def _gla_correction_flops(cfg, shape, chips: int) -> float:
    """GLA chunk-scan FLOPs hidden when cost-unroll was capped (n_chunks>32).

    Applies only to SSM-family prefill cells (xlstm prefill_32k): the dry-run
    unrolls GLA scans up to 32 chunks; beyond that one chunk body is counted
    and the remaining (n-1) bodies are added here analytically."""
    if cfg.family != "ssm" or shape.kind != "prefill":
        return 0.0
    Q = cfg.ssm_chunk
    n = shape.seq_len // Q
    if n <= 32:
        return 0.0
    d_in = cfg.ssm_expand * cfg.d_model
    nh = cfg.num_heads
    dk = d_in // nh
    dv = dk + 1  # normalizer column
    per_chunk_head = 2.0 * Q * Q * (dk + dv) + 4.0 * Q * dk * dv
    n_mlstm = cfg.num_layers - cfg.num_layers // cfg.slstm_period
    tokens_scale = shape.global_batch  # per-batch-row scans
    return per_chunk_head * nh * (n - 1) * n_mlstm * tokens_scale / chips


def model_flops(cfg, shape, chips: int) -> Dict[str, float]:
    n_total = cfg.param_count(active_only=False)
    n_active = cfg.param_count(active_only=True) if cfg.family == "moe" else n_total
    if shape.kind == "train":
        g = 6.0 * n_active * shape.global_batch * shape.seq_len
        g_peft = 4.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        g = g_peft = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:  # decode: one token per sequence
        g = g_peft = 2.0 * n_active * shape.global_batch
    return {"model_flops_dev": g / chips, "model_flops_peft_dev": g_peft / chips,
            "n_active": n_active, "n_total": n_total}


def analytic_memory(cfg, shape, chips: int, tp: int, dp: int) -> Dict[str, float]:
    """Per-device bytes: params + (cache | activations) under the baseline
    layout (what the TPU compiler would actually keep in HBM)."""
    p_total = cfg.param_count() * 2.0
    # attention weights replicated when heads aren't TP-shardable (kvscan
    # mode); everything else shards over tp.  Conservative: shard all by tp.
    params_dev = p_total / tp
    act = 0.0
    cache = 0.0
    if shape.kind in ("train", "prefill"):
        toks_dev = shape.global_batch * shape.seq_len / chips
        layers_live = 1 if cfg.scan_layers and cfg.remat else cfg.num_layers
        # remat keeps ~1 layer of activations + the scan carry + logits slice
        act = toks_dev * cfg.d_model * 2.0 * (8 + 2 * layers_live)
        act += toks_dev * 4.0 * 2  # logits lse etc (vocab-sharded)
        if shape.kind == "train":
            act *= 1.5  # bwd workspace
    else:
        dh = cfg.resolved_head_dim()
        if cfg.attention != "none":
            n_kv_layers = (cfg.layers_of("attention")
                           if cfg.family == "hybrid" else cfg.num_layers)
            cache = (n_kv_layers * shape.global_batch * shape.seq_len *
                     cfg.num_kv_heads * dh * 2 * 2.0) / chips
        if cfg.family in ("hybrid", "ssm"):
            d_in = cfg.ssm_expand * cfg.d_model
            nh = d_in // cfg.ssm_head_dim if cfg.family == "hybrid" else cfg.num_heads
            st = cfg.ssm_state if cfg.family == "hybrid" else (d_in // cfg.num_heads)
            n_ssm = (cfg.layers_of("mamba") if cfg.family == "hybrid"
                     else cfg.num_layers)
            cache += n_ssm * shape.global_batch * nh * st * (
                cfg.ssm_head_dim if cfg.family == "hybrid" else st + 1) * 4.0 / min(chips, tp * dp)
    return {"params_dev": params_dev, "act_dev": act, "cache_dev": cache,
            "analytic_total_dev": params_dev + act + cache}


def tpu_memory_bytes(cfg, shape, chips: int, tp: int) -> float:
    """TPU-corrected HBM traffic per device per step.

    The CPU backend's `bytes accessed` is inflated by weak fusion and f32
    operand copies of bf16 weights (no native bf16 GEMM on CPU); a TPU build
    reads weights once per pass and streams fused activations.  Model:
    weights x passes (1 fwd / 3 train: fwd + remat recompute + bwd-transpose)
    + activations x ~8 fused read/write passes (+ KV cache read for decode).
    """
    p_bytes = cfg.param_count() * 2.0 / tp
    if shape.kind == "train":
        passes = 3.0
        toks_dev = shape.global_batch * shape.seq_len / chips
        layers = cfg.num_layers
        act = toks_dev * cfg.d_model * 2.0 * layers * 8.0
        return p_bytes * passes + act
    if shape.kind == "prefill":
        toks_dev = shape.global_batch * shape.seq_len / chips
        act = toks_dev * cfg.d_model * 2.0 * cfg.num_layers * 4.0
        return p_bytes + act
    # decode: weights + full KV/SSM-state read per token step
    dh = cfg.resolved_head_dim()
    cache = 0.0
    if cfg.attention != "none":
        n_kv = (cfg.layers_of("attention")
                if cfg.family == "hybrid" else cfg.num_layers)
        cache = n_kv * shape.global_batch * shape.seq_len * cfg.num_kv_heads * dh * 2 * 2.0 / chips
    return p_bytes + cache


def analyze(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not rec.get("ok"):
        return None
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    chips = rec["chips"]
    flops = rec["cost"]["per_device_flops"]
    flops += _slstm_correction_flops(cfg, shape, chips, train=True)
    flops += _gla_correction_flops(cfg, shape, chips)
    byts = rec["cost"]["per_device_bytes"]
    coll = rec["cost"]["per_device_collective_bytes"]
    wire = rec["cost"].get("per_device_collective_wire_bytes")
    t_c = flops / PEAK
    t_m = byts / HBM_BW
    t_m_tpu = tpu_memory_bytes(cfg, shape, chips, rec.get("tp", 16)) / HBM_BW
    t_n = coll / ICI_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])[0]
    dom_tpu = max(("compute", t_c), ("memory", t_m_tpu), ("collective", t_n),
                  key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape, chips)
    mem = analytic_memory(cfg, shape, chips, rec.get("tp", 16), rec.get("dp", 16))
    hlo_mem = rec.get("full", {}).get("memory", {}).get("total_bytes")
    bound = max(t_c, t_m, t_n)
    bound_tpu = max(t_c, t_m_tpu, t_n)
    useful = mf["model_flops_dev"] / PEAK  # time the "useful" math needs
    row = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "attn_mode": rec.get("attn_mode", "?"), "chips": chips,
        "compute_s": t_c, "memory_s": t_m, "memory_tpu_s": t_m_tpu,
        "collective_s": t_n,
        "dominant": dom, "dominant_tpu": dom_tpu,
        "model_hlo_ratio": mf["model_flops_dev"] / max(flops, 1e-9),
        "peft_hlo_ratio": mf["model_flops_peft_dev"] / max(flops, 1e-9),
        "roofline_frac": useful / max(bound, 1e-12),
        "roofline_frac_tpu": useful / max(bound_tpu, 1e-12),
        "hbm_fit_analytic": mem["analytic_total_dev"] <= HBM,
        "analytic_mem_GiB": mem["analytic_total_dev"] / 2**30,
        "hlo_mem_GiB": (hlo_mem / 2**30) if hlo_mem else None,
        "flops_dev": flops, "bytes_dev": byts, "coll_bytes_dev": coll,
        "coll_wire_s": (wire / ICI_BW) if wire else None,
        "tag": rec.get("tag", ""),
    }
    return row


HINTS = {
    "compute": "compute-bound: reclaim masked/redundant FLOPs (exact-causal "
               "attention, drop remat on cheap blocks, fuse adapter GEMMs)",
    "memory": "HBM-bound: cut activation/cache traffic (flash tiling, bf16 "
              "cache, fuse elementwise chains, wider arithmetic intensity)",
    "collective": "ICI-bound: reshard to cut gather/reduce bytes (SP residual, "
                  "rs+ag instead of all-reduce, EP-major expert layout, "
                  "overlap with compute)",
}


def run() -> List[str]:
    rows: List[str] = []
    table: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        rec = json.load(open(path))
        if rec.get("tag"):
            continue  # hillclimb variants reported in §Perf, not the base table
        r = analyze(rec)
        if r is None:
            rows.append(f"roofline/{rec.get('arch')}__{rec.get('shape')}__{rec.get('mesh')},0.0,FAILED:{rec.get('error','?')[:60]}")
            continue
        table.append(r)
        rows.append(
            f"roofline/{r['arch']}__{r['shape']}__{r['mesh']},"
            f"{max(r['compute_s'], r['memory_tpu_s'], r['collective_s'])*1e6:.1f},"
            f"dom={r['dominant_tpu']};frac={r['roofline_frac_tpu']:.3f};"
            f"c={r['compute_s']*1e3:.2f}ms;m={r['memory_tpu_s']*1e3:.2f}ms;"
            f"n={r['collective_s']*1e3:.2f}ms;6ND/HLO={r['model_hlo_ratio']:.2f}"
        )
    if table:
        os.makedirs(OUT, exist_ok=True)
        import csv as _csv

        with open(os.path.join(OUT, "roofline.csv"), "w", newline="") as f:
            w = _csv.DictWriter(f, fieldnames=list(table[0].keys()))
            w.writeheader()
            w.writerows(table)
        with open(os.path.join(OUT, "roofline.md"), "w") as f:
            f.write("| arch | shape | mesh | attn | compute s | memory s (HLO) | "
                    "memory s (TPU-corr) | collective s | dom (HLO) | dom (TPU) "
                    "| 6ND/HLO | 4ND/HLO | roofline frac (TPU) | mem/dev GiB | "
                    "fix hint |\n")
            f.write("|---" * 15 + "|\n")
            for r in sorted(table, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
                f.write(
                    f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['attn_mode']} "
                    f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} "
                    f"| {r['memory_tpu_s']:.3e} "
                    f"| {r['collective_s']:.3e} | {r['dominant']} "
                    f"| **{r['dominant_tpu']}** "
                    f"| {r['model_hlo_ratio']:.2f} | {r['peft_hlo_ratio']:.2f} "
                    f"| {r['roofline_frac_tpu']:.3f} | {r['analytic_mem_GiB']:.2f} "
                    f"| {HINTS[r['dominant_tpu']]} |\n"
                )
    return rows
