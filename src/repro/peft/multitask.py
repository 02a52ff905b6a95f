"""Multi-task adapter state + the spatially-fused Dispatch/Aggregate rules.

``TaskSegments`` is the static row->task map of a spatially fused (hTask)
batch — shapes are constant across iterations within a bucket (§3.4.1(i)),
so the map is compile-time constant and the grouped kernels see static
segment plans.

``MultiTaskAdapters`` builds one stacked parameter tree per PEFT *kind*
(LoRA tasks stack together, VeRA tasks together, ...), mirroring the
backbone's stacked-layer layout so the model's layer scan slices adapters
alongside backbone weights.  Everything method-specific — which sites a
kind attaches to, its ParamSpecs, its Dispatch/Aggregate rule, its slot
scale — comes from the :mod:`repro.peft.methods` registry; this module
never branches on a method's name.

``MultiTaskContext`` realizes Dispatch (route fused-batch rows to their
task's adapter) and Aggregate (merge add/mul contributions into the BaseOp
output) — the horizontal adapter fusion of §3.4.3: one grouped computation
per kind covers all tasks.  Soft-prompt methods additionally surface
per-row k/v prefix rows to packed attention via ``attn_prefix``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.models.layers import ParamSpec, abstract, materialize
from repro.peft.hooks import AdapterContext
from repro.peft.methods import (
    AdapterConfig,
    ApplyContext,
    base_op_dims,
    get_method,
    supports_attention_prefix,
)


@dataclass(frozen=True)
class TaskSegments:
    """Row-level task layout of a fused batch (static)."""

    row_task: Tuple[int, ...]  # len == fused batch rows; values in [0, n_tasks)
    n_tasks: int

    @staticmethod
    def contiguous(rows_per_task: Sequence[int]) -> "TaskSegments":
        rt: List[int] = []
        for t, n in enumerate(rows_per_task):
            rt.extend([t] * n)
        return TaskSegments(tuple(rt), len(rows_per_task))

    @property
    def batch(self) -> int:
        return len(self.row_task)

    def relabel(self, member_ids: Sequence[int]) -> "TaskSegments":
        """Re-index rows onto the member list (global -> local task ids).

        The local view is what compiled hTask steps see: their per-task loss
        output is sized to the members only, so the compiled computation is
        independent of the GLOBAL task census — the engine's signature cache
        can reuse a step across re-plans that shift global indices."""
        lookup = {g: l for l, g in enumerate(member_ids)}
        return TaskSegments(tuple(lookup[t] for t in self.row_task), len(member_ids))

    def row_task_array(self) -> np.ndarray:
        return np.asarray(self.row_task, np.int32)

    def token_task(self, seq_len: int) -> jax.Array:
        return jnp.repeat(jnp.asarray(self.row_task_array()), seq_len)

    def per_task_loss(self, per_token_loss: jax.Array, loss_mask: jax.Array) -> jax.Array:
        """[n_tasks] mean loss per task — per-task isolation (Eq. 1-2)."""
        rt = jnp.asarray(self.row_task_array())
        losses = jnp.zeros((self.n_tasks,), jnp.float32).at[rt].add(
            per_token_loss.sum(axis=-1)
        )
        counts = jnp.zeros((self.n_tasks,), jnp.float32).at[rt].add(
            loss_mask.astype(jnp.float32).sum(axis=-1)
        )
        return losses / jnp.maximum(counts, 1.0)


class MultiTaskAdapters:
    """Builds & applies stacked multi-task adapter params for one backbone.

    Slot-stable capacity allocation (online serving): each kind's stack is
    sized ``kind_capacity[kind]`` >= live task count, and each task owns an
    explicit ``task_slot`` within its kind stack.  Keeping slots and
    capacities stable across task arrival/departure keeps every adapter
    leaf's *shape* stable, which is what lets the engine reuse compiled
    hTask steps across re-plans (no retrace on churn).  Unused slots hold
    fresh-init values that no batch row ever routes to.  Leaves a method
    declares ``shared_params`` carry NO task axis: they are frozen,
    deterministic, and shared by every tenant of the kind.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        task_cfgs: Sequence[AdapterConfig],
        kind_capacity: Optional[Dict[str, int]] = None,
        kind_rank: Optional[Dict[str, int]] = None,
        task_slot: Optional[Sequence[int]] = None,
    ):
        self.cfg = cfg
        self.task_cfgs = tuple(task_cfgs)
        self.dims = base_op_dims(cfg)
        self.attention_ok = supports_attention_prefix(cfg)
        # group tasks by kind; record slot of each task within its kind stack
        self.kind_tasks: Dict[str, List[int]] = {}
        for i, tc in enumerate(task_cfgs):
            self.kind_tasks.setdefault(tc.kind, []).append(i)
        if task_slot is None:
            self.task_slot = np.full((len(task_cfgs),), -1, np.int32)
            for kind, ids in self.kind_tasks.items():
                for slot, tid in enumerate(ids):
                    self.task_slot[tid] = slot
        else:
            self.task_slot = np.asarray(task_slot, np.int32)
            assert self.task_slot.shape == (len(task_cfgs),)
            for kind, ids in self.kind_tasks.items():
                slots = [int(self.task_slot[i]) for i in ids]
                assert len(set(slots)) == len(slots) and min(slots, default=0) >= 0, \
                    f"slot collision for kind {kind}: {slots}"
        # stack rank per kind: max over members, never below the given floor
        # (a surviving task trains the FULL stack rank, so rank never shrinks
        # while any member survives — see ModelGenerator._kind_rank)
        self.kind_rank: Dict[str, int] = {}
        self.kind_capacity: Dict[str, int] = {}
        for kind, ids in self.kind_tasks.items():
            r = max(self.task_cfgs[i].rank for i in ids)
            if kind_rank and kind in kind_rank:
                r = max(r, kind_rank[kind])
            self.kind_rank[kind] = r
            need = max(int(self.task_slot[i]) for i in ids) + 1
            cap = need
            if kind_capacity and kind in kind_capacity:
                cap = max(cap, kind_capacity[kind])
            self.kind_capacity[kind] = cap

    # ------------------------------------------------------------------

    def kind_targets(self, kind: str) -> Tuple[str, ...]:
        """Union of the member tasks' requested BaseOp targets."""
        tgts = set().union(*(self.task_cfgs[i].targets
                             for i in self.kind_tasks[kind]))
        return tuple(sorted(tgts))

    def kind_sites(self, kind: str,
                   targets_filter: Optional[set] = None) -> Dict[str, Tuple[int, int]]:
        """The method's attach sites, restricted to a BaseOp-dims filter."""
        dims = self.dims if targets_filter is None else {
            n: d for n, d in self.dims.items() if n in targets_filter}
        return get_method(kind).sites(self.kind_targets(kind), dims,
                                      attention=self.attention_ok)

    def _per_layer_spec(self, targets_filter=None) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for kind in self.kind_tasks:
            method = get_method(kind)
            rank = self.kind_rank[kind]
            kspec: Dict[str, Any] = {}
            for site, (din, dout) in self.kind_sites(kind, targets_filter).items():
                kspec[site] = method.param_specs(rank, din, dout,
                                                self.kind_capacity[kind])
            if kspec:
                out[kind] = kspec
        return out

    def _stack(self, spec: Dict[str, Any], *dims: int) -> Dict[str, Any]:
        def f(s: ParamSpec) -> ParamSpec:
            return ParamSpec(tuple(dims) + s.shape, ("layers",) * len(dims) + s.axes,
                             s.init, s.scale, s.dtype)
        return jax.tree.map(f, spec, is_leaf=lambda x: isinstance(x, ParamSpec))

    def spec(self) -> Any:
        """Adapter ParamSpec tree mirroring the backbone's layer layout."""
        cfg = self.cfg
        per = self._per_layer_spec()
        if cfg.family in ("dense", "vlm", "moe", "audio"):
            return self._stack(per, cfg.num_layers)
        if cfg.family == "hybrid":
            # the model's layout: per kind [period, index within it, ...]
            # (the weight-shared attention block unstacked); MLP sites sit
            # on every layer that has an MLP
            n_per, pat = cfg.n_periods, cfg.layer_pattern
            ssm_targets = {"ssm_in", "ssm_out"}
            attn_targets = set(self.dims) - ssm_targets
            mlp_targets = {n for n in attn_targets if n.startswith("mlp_")}
            mamba_targets = ssm_targets | (mlp_targets if cfg.mamba_mlp else set())
            out = {"mamba": self._stack(self._per_layer_spec(mamba_targets),
                                        n_per, pat.count("mamba"))}
            attn = self._per_layer_spec(attn_targets)
            if cfg.shared_attention:
                out["shared_attn"] = attn
            else:
                out["attn"] = self._stack(attn, n_per, pat.count("attention"))
            return out
        if cfg.family == "ssm":
            n_super = cfg.num_layers // cfg.slstm_period
            return {
                "mlstm": self._stack(self._per_layer_spec(), n_super, cfg.slstm_period - 1),
                "slstm": self._stack(self._per_layer_spec({"ssm_in", "ssm_out"}), n_super),
            }
        raise ValueError(cfg.family)

    def init(self, key: jax.Array) -> Any:
        return self._post_init(materialize(self.spec(), key))

    def abstract(self) -> Any:
        return abstract(self.spec())

    def _post_init(self, params: Any) -> Any:
        """Deterministic per-method fixups: structural masks (Diff-Pruning
        rows), shared frozen matrices (VeRA A/B).  Pure in (site, dims), so
        every stack rebuild reproduces identical values — migration then
        never has to special-case them."""
        site_dims = {k: self.kind_sites(k) for k in self.kind_tasks}

        def walk(node: Any) -> Any:
            if not isinstance(node, dict):
                return node
            out = {}
            for k, v in node.items():
                if k in self.kind_tasks and isinstance(v, dict):
                    method = get_method(k)
                    out[k] = {
                        site: method.post_init(dict(leaves), site,
                                               *site_dims[k].get(site, (0, 0)))
                        if isinstance(leaves, dict) else leaves
                        for site, leaves in v.items()
                    }
                else:
                    out[k] = walk(v)
            return out

        return walk(params)

    # ------------------------------------------------------------------

    def scales(self, kind: str) -> np.ndarray:
        """Per-slot aggregate scale, sized to the kind's stack capacity."""
        method = get_method(kind)
        out = np.ones((self.kind_capacity[kind],), np.float32)
        for i in self.kind_tasks[kind]:
            out[int(self.task_slot[i])] = method.slot_scale(self.task_cfgs[i])
        return out

    def slot_values(self, kind: str, per_task: Dict[int, float],
                    fill: float = 0.0) -> np.ndarray:
        """Scatter per-task values to their slots in a capacity-sized vector."""
        out = np.full((self.kind_capacity[kind],), fill, np.float32)
        for i in self.kind_tasks[kind]:
            if i in per_task:
                out[int(self.task_slot[i])] = per_task[i]
        return out

    def kind_row_slots(self, segments: TaskSegments, kind: str) -> np.ndarray:
        """Per batch-row slot within the ``kind`` stack; -1 => not this kind."""
        members = set(self.kind_tasks[kind])
        rt = segments.row_task_array()
        slots = np.full_like(rt, -1)
        for r, t in enumerate(rt):
            if t in members:
                slots[r] = self.task_slot[t]
        return slots

    def ctx_factory(self, segments: TaskSegments):
        """Returns the per-layer adapter-context factory for Model.forward."""
        kind_slots = {
            kind: jnp.asarray(self.kind_row_slots(segments, kind))
            for kind in self.kind_tasks
        }
        kind_scales = {kind: jnp.asarray(self.scales(kind)) for kind in self.kind_tasks}
        return self.ctx_factory_from_slots(kind_slots, kind_scales)

    def ctx_factory_from_slots(self, kind_slots: Dict[str, jax.Array],
                               kind_scales: Optional[Dict[str, jax.Array]] = None):
        """Adapter-context factory over EXPLICIT per-row slot vectors.

        ``kind_slots[kind]`` is [B] int32 (slot in that kind's stack, -1 =
        row not of this kind).  Unlike :meth:`ctx_factory`, the vectors may
        be TRACED arrays — formal inputs of a jitted step — so one compiled
        task-aware decode step serves ANY row->task binding: requests bind
        and unbind against the pool without retracing (the serving layer's
        slot-stable decode contract)."""
        if kind_scales is None:
            kind_scales = {kind: jnp.asarray(self.scales(kind))
                           for kind in self.kind_tasks}

        def factory(layer_adapters: Any) -> AdapterContext:
            return MultiTaskContext(layer_adapters, kind_slots, kind_scales)

        return factory

    def decode_row_slots(self, row_task: Sequence[int]) -> Dict[str, np.ndarray]:
        """Per-kind [B] slot vectors for an ad-hoc row->task map (decode
        pool bindings; -1 = unbound row).  Host-side numpy — feed as traced
        inputs to a step built with :meth:`ctx_factory_from_slots`."""
        rt = np.asarray(row_task, np.int32)
        out: Dict[str, np.ndarray] = {}
        for kind, ids in self.kind_tasks.items():
            members = set(ids)
            slots = np.full(rt.shape, -1, np.int32)
            for r, t in enumerate(rt):
                if t in members:
                    slots[r] = self.task_slot[t]
            out[kind] = slots
        return out


class MultiTaskContext(AdapterContext):
    """Grouped Dispatch/Aggregate over a fused batch: one contribution per
    PEFT kind, each produced by that kind's registered method."""

    def __init__(self, layer_adapters, kind_slots, kind_scales):
        self.ad = layer_adapters or {}
        self.kind_slots = kind_slots
        self.kind_scales = kind_scales

    def has(self, name: str) -> bool:
        return any(name in kspec for kspec in self.ad.values())

    def _site_ctx(self, kind: str, d_in: int = 0, d_out: int = 0,
                  base_weight=None) -> ApplyContext:
        slots = self.kind_slots[kind]
        return ApplyContext(
            slots=slots,
            gate=(slots >= 0).astype(jnp.float32),
            scale=self.kind_scales[kind],
            d_in=d_in, d_out=d_out, base_weight=base_weight,
        )

    def apply(self, name: str, x: jax.Array, base_out: jax.Array,
              w: Optional[jax.Array] = None) -> jax.Array:
        """Dispatch/Aggregate over the fused batch.  All adapter params are
        gathered per *batch row* (B entries), never per token — memory-lean
        on the XLA path and block-aligned for the Pallas path.  The site
        output is ``(base_out + sum_k add_k) * prod_k mul_k``; each method's
        contribution is identity on rows it doesn't own (one task per row,
        one kind per task, so cross-kind terms never mix on a row)."""
        B, S = x.shape[0], x.shape[1]
        d_in = int(np.prod(x.shape[2:]))
        d_out = int(np.prod(base_out.shape[2:]))
        x3 = x.reshape(B, S, d_in)
        out3 = base_out.reshape(B, S, d_out)
        w2 = w.reshape(d_in, d_out) if w is not None else None
        add = jnp.zeros_like(out3, dtype=jnp.float32)
        mul = None
        for kind, kspec in self.ad.items():
            if name not in kspec:
                continue
            ctx = self._site_ctx(kind, d_in, d_out, w2)
            a, m1 = get_method(kind).apply(kspec[name], x3, out3, ctx)
            if a is not None:
                add = add + a
            if m1 is not None:
                mul = m1 if mul is None else mul * m1
        y = out3.astype(jnp.float32) + add
        if mul is not None:
            y = y * mul
        return y.astype(base_out.dtype).reshape(base_out.shape)

    def attn_prefix(self):
        """Collect every soft-prompt kind's per-row k/v prefix rows for this
        layer; concatenated along the prefix-token axis."""
        pks, pvs, keeps = [], [], []
        for kind, kspec in self.ad.items():
            p = kspec.get("attn_prefix")
            if p is None:
                continue
            ctx = self._site_ctx(kind)
            pref = get_method(kind).attn_prefix(p, ctx)
            if pref is None:
                continue
            pk, pv = pref  # [B, P, kv_dim]
            pks.append(pk)
            pvs.append(pv)
            keeps.append(jnp.broadcast_to(ctx.gate[:, None], pk.shape[:2]))
        if not pks:
            return None
        return (jnp.concatenate(pks, axis=1), jnp.concatenate(pvs, axis=1),
                jnp.concatenate(keeps, axis=1))
