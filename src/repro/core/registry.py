"""ModelGenerator + ``register_tasks()`` — dynamic multi-task attachment (§3.2).

The functional analogue of the paper's hook-based on-the-fly registration:
the backbone is instantiated ONCE; task arrival/completion rebuilds only the
stacked adapter pytree (migrating surviving tasks' adapter values and
optimizer moments into the new stack) and invalidates the step cache for the
new task-set signature.  No backbone re-init, ever.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig, get_config
from repro.core.task import PEFTTask
from repro.models.transformer import Model, build_model
from repro.peft.methods import shared_leaf
from repro.peft.multitask import MultiTaskAdapters
from repro.train.optimizer import AdamWState, adamw_init


def _task_axis(depth: int) -> int:
    return depth  # stacking prepends `depth` layer dims before the task dim


def _group_depths(cfg: ArchConfig) -> Dict[str, int]:
    if cfg.family in ("dense", "vlm", "moe", "audio"):
        return {"": 1}
    if cfg.family == "hybrid":
        if cfg.shared_attention:
            return {"mamba": 2, "shared_attn": 0}
        return {"mamba": 2, "attn": 2}
    if cfg.family == "ssm":
        return {"mlstm": 2, "slstm": 1}
    raise ValueError(cfg.family)


@dataclass
class RegisteredTasks:
    tasks: List[PEFTTask]
    mta: MultiTaskAdapters
    adapter_params: Any
    opt_state: AdamWState

    def signature(self) -> Tuple:
        return tuple((t.task_id, t.adapter.kind, t.adapter.rank,
                      int(self.mta.task_slot[i])) for i, t in enumerate(self.tasks))

    def task_index(self, task_id: str) -> int:
        for i, t in enumerate(self.tasks):
            if t.task_id == task_id:
                return i
        raise KeyError(task_id)


def slice_task_tree(cfg: ArchConfig, mta: MultiTaskAdapters, tree: Any,
                    task_index: int) -> Any:
    """Extract ONE task's adapter slices from the stacked tree (task axis
    removed) — the standalone artifact a completed tenant checkpoints out.
    Shared (method-declared ``shared_params``) leaves carry no task axis and
    are included whole, so the artifact is self-contained per the method's
    checkpoint schema."""
    kind = mta.task_cfgs[task_index].kind
    slot = int(mta.task_slot[task_index])
    depths = _group_depths(cfg)

    def walk(node: Any, depth: int, in_kind: bool, name=None) -> Any:
        if not isinstance(node, dict):
            if node is None or not in_kind:
                return None
            if name is not None and shared_leaf(kind, name):
                return node  # shared leaf: no task axis, carried whole
            return jax.lax.index_in_dim(node, slot, axis=depth, keepdims=False)
        out = {}
        for k, v in node.items():
            if k in mta.kind_tasks and not in_kind:
                if k != kind:
                    continue
                out[k] = walk(v, depth, True)
            else:
                sub = walk(v, depth, in_kind, k)
                if sub is not None and not (isinstance(sub, dict) and not sub):
                    out[k] = sub
        return out

    if "" in depths:
        return walk(tree, depths[""], False)
    return {gk: walk(tree.get(gk, {}), d, False)
            for gk, d in depths.items() if gk in tree}


def load_task_tree(cfg: ArchConfig, mta: MultiTaskAdapters, tree: Any,
                   task_index: int, sub: Any, strict: bool = False) -> Any:
    """Write a single-task adapter subtree back into its stack slot (warm
    start).  Rank-padded: a subtree saved at a smaller stack rank loads into
    the leading rank slice, zeros elsewhere preserved by the fresh init.
    An incompatible leaf (bigger rank, different layer stacking) keeps the
    fresh init — or raises with ``strict=True`` so a caller can surface the
    failed warm start instead of silently cold-starting the tenant."""
    kind = mta.task_cfgs[task_index].kind
    slot = int(mta.task_slot[task_index])
    depths = _group_depths(cfg)

    def skip(node, src):
        if strict:
            raise ValueError(
                f"warm-start leaf shape {src.shape} incompatible with stack "
                f"leaf {node.shape} (task axis {kind}[{slot}])")
        return node

    def walk(node: Any, sub_node: Any, depth: int, in_kind: bool, name=None) -> Any:
        if not isinstance(node, dict):
            if node is None or sub_node is None or not in_kind:
                return node
            if name is not None and shared_leaf(kind, name):
                # shared frozen leaf: deterministically regenerated by the
                # method's post_init — never written from an artifact
                return node
            src = jnp.asarray(sub_node)
            if src.ndim != node.ndim - 1:
                return skip(node, src)
            head, tail = node.shape[:depth], node.shape[depth + 1:]
            s_head, s_tail = src.shape[:depth], src.shape[depth:]
            if s_head != head:
                return skip(node, src)
            if any(s > t for s, t in zip(s_tail, tail)):
                # a WIDER artifact (saved from a cohort whose stack padded
                # this task's slot past its own rank, e.g. a higher-rank
                # co-resident) loads losslessly iff the overflow region is
                # exactly zero — which rank padding is, since zero-init
                # pad columns receive zero gradients forever.  Nonzero
                # overflow means genuinely incompatible data: skip/raise.
                clip = src[(slice(None),) * depth
                           + tuple(slice(0, min(s, t))
                                   for s, t in zip(s_tail, tail))]
                if int(jnp.count_nonzero(src)) != int(jnp.count_nonzero(clip)):
                    return skip(node, src)
                src = clip
                s_tail = src.shape[depth:]
            idx = ((slice(None),) * depth + (slot,)
                   + tuple(slice(0, s) for s in s_tail))
            return node.at[idx].set(src.astype(node.dtype))
        out = {}
        for k, v in node.items():
            if k in mta.kind_tasks and not in_kind:
                if k == kind and isinstance(sub_node, dict) and k in sub_node:
                    out[k] = walk(v, sub_node[k], depth, True)
                else:
                    out[k] = v
            else:
                s = sub_node.get(k) if isinstance(sub_node, dict) else None
                out[k] = walk(v, s, depth, in_kind, k)
        return out

    if "" in depths:
        return walk(tree, sub, depths[""], False)
    return {gk: (walk(tree[gk], (sub or {}).get(gk), d, False)
                 if gk in (sub or {}) else tree[gk])
            for gk, d in depths.items() if gk in tree}


class ModelGenerator:
    """Builds the PEFT model for an instance and manages task registration."""

    def __init__(self, arch: str | ArchConfig, tp_size: int = 1, seed: int = 0):
        self.cfg = get_config(arch) if isinstance(arch, str) else arch
        self.model: Model = build_model(self.cfg, tp_size=tp_size)
        self._key = jax.random.PRNGKey(seed)
        self.backbone_params: Optional[Any] = None
        self.registered: Optional[RegisteredTasks] = None
        # Slot-stability state: stack capacity and rank floor per kind are
        # monotone across attach/detach (shrunk only by compact()) so leaf
        # shapes — and therefore compiled hTask steps — survive churn.
        self._kind_capacity: Dict[str, int] = {}
        self._kind_rank: Dict[str, int] = {}
        # Pre-reserved slots per kind: a serving controller sets this so the
        # first few tenant arrivals land in already-allocated stacks instead
        # of forcing a capacity growth (= full recompile).
        self.capacity_floor: int = 0

    # ------------------------------------------------------------------

    def init_backbone(self) -> Any:
        if self.backbone_params is None:
            self._key, k = jax.random.split(self._key)
            params = self.model.init(k)
            if self.cfg.backbone_dtype == "int8":
                # quantize ONCE at model build: every adapter-capable BaseOp
                # weight becomes {"q": int8, "scale": f32}; the hot path
                # reads the int8 blocks via kops.quant_matmul (PR 9)
                from repro.models.quantize import quantize_backbone

                params = quantize_backbone(params, self.cfg)
            self.backbone_params = params
        return self.backbone_params

    # ------------------------------------------------------------------

    def register_tasks(self, new_tasks: Sequence[PEFTTask]) -> RegisteredTasks:
        """Add tasks to (or rebuild) the in-flight instance — §3.2 API."""
        old = self.registered
        tasks = list(old.tasks) if old else []
        existing = {t.task_id for t in tasks}
        for t in new_tasks:
            if t.task_id in existing:
                raise ValueError(f"duplicate task_id {t.task_id}")
            tasks.append(t)
        return self._rebuild(tasks, old)

    def deregister_tasks(self, task_ids: Sequence[str]) -> RegisteredTasks:
        old = self.registered
        assert old is not None
        drop = set(task_ids)
        tasks = [t for t in old.tasks if t.task_id not in drop]
        return self._rebuild(tasks, old)

    def compact(self) -> RegisteredTasks:
        """Re-pack slots densely and shrink capacities to the live task set,
        physically freeing departed tenants' adapter/moment memory.  Stack
        ranks do NOT shrink (survivors train the full stack rank).  All
        compiled steps are invalidated by the shape change — call when
        occupancy is low, not on every detach."""
        old = self.registered
        assert old is not None
        return self._rebuild(list(old.tasks), old, compact=True)

    # ------------------------------------------------------------------

    def _slot_plan(self, tasks: List[PEFTTask], old: Optional[RegisteredTasks]):
        """Slot-stable assignment: survivors keep their slots, new tasks take
        the lowest free slot; capacity doubles when a kind's stack is full."""
        old_ids = {t.task_id: i for i, t in enumerate(old.tasks)} if old else {}
        slots = np.full((len(tasks),), -1, np.int32)
        used: Dict[str, set] = {}
        for i, t in enumerate(tasks):
            kind = t.adapter.kind
            used.setdefault(kind, set())
            if old is not None and t.task_id in old_ids:
                oi = old_ids[t.task_id]
                if old.tasks[oi].adapter.kind != kind:
                    continue
                s = int(old.mta.task_slot[oi])
                slots[i] = s
                used[kind].add(s)
        caps = dict(self._kind_capacity)
        if self.capacity_floor:
            for kind in {t.adapter.kind for t in tasks}:
                caps[kind] = max(caps.get(kind, 0), self.capacity_floor)
        for i, t in enumerate(tasks):
            if slots[i] >= 0:
                continue
            kind = t.adapter.kind
            cap = caps.get(kind, 0)
            free = [s for s in range(cap) if s not in used[kind]]
            if free:
                s = free[0]
            else:
                s = max(used[kind], default=-1) + 1
                caps[kind] = max(cap * 2, s + 1)  # amortized growth
            slots[i] = s
            used[kind].add(s)
        # drop capacity/rank floors for kinds with no live tasks
        live_kinds = {t.adapter.kind for t in tasks}
        caps = {k: v for k, v in caps.items() if k in live_kinds}
        ranks = {k: v for k, v in self._kind_rank.items() if k in live_kinds}
        return slots, caps, ranks

    def _rebuild(self, tasks: List[PEFTTask], old: Optional[RegisteredTasks],
                 compact: bool = False) -> RegisteredTasks:
        if compact:
            # dense re-pack: default slot assignment, capacities = live counts
            live_kinds = {t.adapter.kind for t in tasks}
            slots, caps = None, None
            ranks = {k: v for k, v in self._kind_rank.items() if k in live_kinds}
        else:
            slots, caps, ranks = self._slot_plan(tasks, old)
        mta = MultiTaskAdapters(self.cfg, [t.adapter for t in tasks],
                                kind_capacity=caps, kind_rank=ranks,
                                task_slot=slots)
        self._kind_capacity = dict(mta.kind_capacity)
        self._kind_rank = dict(mta.kind_rank)
        self._key, k = jax.random.split(self._key)
        params = mta.init(k)
        opt = adamw_init(params)
        if old is not None and old.tasks:
            params, opt = self._migrate(old, mta, params, opt, tasks)
        self.registered = RegisteredTasks(tasks, mta, params, opt)
        return self.registered

    def _migrate(
        self,
        old: RegisteredTasks,
        new_mta: MultiTaskAdapters,
        new_params: Any,
        new_opt: AdamWState,
        tasks: List[PEFTTask],
    ) -> Tuple[Any, AdamWState]:
        """Copy surviving tasks' adapter values + moments into the new stacks."""
        old_ids = {t.task_id: i for i, t in enumerate(old.tasks)}
        depths = _group_depths(self.cfg)

        def migrate_group(old_tree, new_tree, old_m, new_m, kind, depth):
            old_slots = {}
            for tid_new, t in enumerate(tasks):
                if t.adapter.kind != kind or t.task_id not in old_ids:
                    continue
                old_global = old_ids[t.task_id]
                if old.tasks[old_global].adapter.kind != kind:
                    continue
                new_slot = new_mta.task_slot[tid_new]
                old_slot = old.mta.task_slot[old_global]
                old_slots[int(new_slot)] = int(old_slot)
            if not old_slots:
                return new_tree, new_m

            ax = _task_axis(depth)

            def copy_leaf(new_leaf, old_leaf, name=None):
                if old_leaf is None or new_leaf is None:
                    return new_leaf
                if name is not None and shared_leaf(kind, name):
                    # shared frozen leaf: fresh deterministic init IS the
                    # migrated value (rank growth appends new columns/rows
                    # while the trained-against leading slice is unchanged)
                    return new_leaf
                same_head = new_leaf.shape[:ax] == old_leaf.shape[:ax]
                old_tail = old_leaf.shape[ax + 1:]
                new_tail = new_leaf.shape[ax + 1:]
                # rank growth pads: copy into the leading slice (LoRA "b"
                # extra rank-rows stay zero, so the adapter delta is exact)
                grows = (len(new_tail) == len(old_tail)
                         and all(n >= o for n, o in zip(new_tail, old_tail)))
                if not (same_head and grows):
                    return new_leaf  # incompatible shape: keep fresh init
                out = new_leaf
                for ns, os in old_slots.items():
                    src = jax.lax.index_in_dim(old_leaf, os, axis=ax, keepdims=False)
                    idx = ((slice(None),) * ax + (ns,)
                           + tuple(slice(0, o) for o in old_tail))
                    out = out.at[idx].set(src.astype(out.dtype))
                return out

            def merge(new_node, old_node, name=None):
                # structure-tolerant: a new task may introduce target keys the
                # old stack lacks (kept fresh); dropped keys just disappear
                if not isinstance(new_node, dict):
                    return copy_leaf(new_node, old_node, name)
                if not isinstance(old_node, dict):
                    return new_node
                return {k: merge(v, old_node[k], k) if k in old_node else v
                        for k, v in new_node.items()}

            return merge(new_tree, old_tree), merge(new_m, old_m)

        def walk(new_p, old_p, new_m, old_m, new_v, old_v, group_key, depth):
            # group level: {kind: {target: {leaf}}}
            out_p, out_m, out_v = new_p, new_m, new_v
            for kind in list(new_p.keys()):
                if old_p is None or kind not in old_p:
                    continue
                # only migrate when ranks match (shape compatibility)
                np_, nm = migrate_group(old_p[kind], new_p[kind],
                                        old_m[kind] if old_m else None,
                                        new_m[kind] if new_m else None,
                                        kind, depth)
                _, nv = migrate_group(old_p[kind], new_p[kind],
                                      old_v[kind] if old_v else None,
                                      new_v[kind] if new_v else None,
                                      kind, depth)
                out_p = dict(out_p, **{kind: np_})
                out_m = dict(out_m, **{kind: nm})
                out_v = dict(out_v, **{kind: nv})
            return out_p, out_m, out_v

        depths_map = depths
        if "" in depths_map:
            p2, m2, v2 = walk(new_params, old.adapter_params,
                              new_opt.m, old.opt_state.m,
                              new_opt.v, old.opt_state.v, "", depths_map[""])
            return p2, AdamWState(new_opt.step, m2, v2)
        p_out, m_out, v_out = dict(new_params), dict(new_opt.m), dict(new_opt.v)
        for gk, depth in depths_map.items():
            if gk not in new_params or gk not in old.adapter_params:
                continue
            p2, m2, v2 = walk(new_params[gk], old.adapter_params[gk],
                              new_opt.m[gk], old.opt_state.m[gk],
                              new_opt.v[gk], old.opt_state.v[gk], gk, depth)
            p_out[gk], m_out[gk], v_out[gk] = p2, m2, v2
        return p_out, AdamWState(new_opt.step, m_out, v_out)
