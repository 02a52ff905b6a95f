"""Per-(architecture x shape) sharding-rule selection — DESIGN.md §5.

The baseline layout:
 * train/prefill: batch over ("pod","data"); sequence-sharded residual (SP)
   over "model"; MLP/vocab/experts TP over "model"; attention head-sharded
   ("pairs" flash) when num_heads % tp == 0, else context-parallel q-seq
   sharding ("kvscan" flash) with gathered GQA KV.
 * decode: batch over ("pod","data") (dropped when global_batch < dp);
   KV cache sharded on head_dim over "model" when divisible (keeps the
   cache-append dynamic-update local), else on kv_heads, else on cache_seq;
   long-context (batch=1) shards cache_seq over ("pod","data").
"""
from __future__ import annotations

from typing import Dict

from jax.sharding import Mesh

from repro.configs import ArchConfig, ShapeSpec
from repro.distributed.sharding import ShardingRules


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def tp_size(mesh: Mesh) -> int:
    return _axis_size(mesh, "model")


def attn_mode_for(cfg: ArchConfig, mesh: Mesh) -> str:
    if cfg.attention == "none":
        return "pairs"
    return "pairs" if cfg.num_heads % tp_size(mesh) == 0 else "kvscan"


def rules_for(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> ShardingRules:
    tp = tp_size(mesh)
    dp = _axis_size(mesh, "pod") * _axis_size(mesh, "data")
    r = ShardingRules()

    updates: Dict[str, object] = {}
    if shape.kind in ("train", "prefill"):
        updates["batch"] = ("pod", "data") if shape.global_batch % dp == 0 else None
        updates["seq"] = "model" if shape.seq_len % tp == 0 else None
        updates["heads"] = "model" if cfg.num_heads % tp == 0 else None
        updates["kv_heads"] = "model" if (
            cfg.num_heads % tp == 0 and cfg.num_kv_heads % tp == 0
        ) else None
        updates["ff"] = "model" if (cfg.d_ff or tp) % tp == 0 else None
        updates["experts"] = "model" if (cfg.num_experts % tp == 0 and cfg.num_experts) else None
        updates["ssm_heads"] = "model" if (
            cfg.family in ("hybrid",) and ((cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim) % tp == 0
        ) else None
        updates["ssm_inner"] = "model" if (cfg.ssm_expand * cfg.d_model) % tp == 0 else None
    else:  # decode
        dh = cfg.resolved_head_dim()
        updates["batch"] = ("pod", "data") if shape.global_batch % dp == 0 else None
        updates["seq"] = None
        updates["heads"] = None
        updates["ff"] = "model" if (cfg.d_ff or tp) % tp == 0 else None
        updates["experts"] = "model" if (cfg.num_experts % tp == 0 and cfg.num_experts) else None
        updates["ssm_inner"] = "model" if (cfg.ssm_expand * cfg.d_model) % tp == 0 else None
        updates["ssm_heads"] = None
        # KV cache layout
        if dh % tp == 0:
            updates["head_dim"] = "model"
            updates["cache_seq"] = ("pod", "data") if shape.global_batch < dp else None
        elif cfg.num_kv_heads % tp == 0:
            updates["kv_heads"] = "model"
            updates["cache_seq"] = ("pod", "data") if shape.global_batch < dp else None
        else:
            updates["cache_seq"] = "model"
        updates["cache_batch"] = (
            ("pod", "data") if shape.global_batch % dp == 0 else None
        )
    # vocab: padded to 256 so always divisible by tp<=16
    updates["vocab"] = "model"
    return r.with_updates(**updates)
