"""PEFTEngine: executes an ExecutionPlan as jitted multi-task steps (§3.1).

Spatial multiplexing = one fused batch per hTask (grouped adapters, shared
backbone).  Temporal multiplexing = template-ordered execution of bucket
micro-batches.

Online serving support (task churn):  ``attach_tasks`` / ``detach_tasks``
swap in a new plan without touching unaffected compiled work.  Each compiled
step is keyed by an explicit *hTask signature* — batch geometry plus each
row's (kind, slot) routing and each member's (slot, rank, scale, lr)
hyperparams plus the adapter-stack shape census — deliberately free of
GLOBAL task indices.  A step therefore survives re-plans that renumber the
task list, and with slot-stable adapter stacks (capacity allocation in
``MultiTaskAdapters``) it survives tenant arrival/departure outright: only
buckets whose fused geometry actually changed recompile.

Per-task optimizer isolation: losses are per-task means summed (gradients
are exactly the per-task gradients — Eq. 1-2 isolation), per-task learning
rates enter as lr-scale trees, and member-slot masking confines every
update — values AND AdamW moments AND bias-correction step counts — to the
slots of the tasks actually present in the micro-batch.  A tenant fused
with others therefore optimizes bit-for-bit like a solo run (modulo fusion
numerics), and a NaN guard zeroes a step's update without polluting other
tasks (numerical-failure isolation, §3.2).

The iteration loop is stall-free (MuxServe-style dispatch discipline):
micro-step metrics accumulate on-device, batches double-buffer host→device,
and exactly one explicit device→host transfer happens per iteration.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import ExecutionPlan
from repro.core.registry import ModelGenerator, RegisteredTasks, _group_depths
from repro.models.transformer import Model
from repro.obs.tracing import span
from repro.peft.methods import shared_leaf
from repro.train.optimizer import adamw_update, apply_updates


@dataclass
class StepMetrics:
    loss: float
    per_task_loss: np.ndarray
    tokens: int
    effective_tokens: int
    wall_seconds: float
    # co-serving (token-level decode interleave) — zero when no inference
    # traffic rode along with this training iteration
    decode_tokens: int = 0
    decode_seconds: float = 0.0
    decode_micro_steps: int = 0  # fused micro-steps in the timed segment

    @property
    def decode_token_seconds(self) -> float:
        """Mean wall seconds per decode token of this iteration's batch.

        This is the measured decode-side channel the calibration loop fits
        (``cost_model.calibrate_profile(decode_samples=...)``) so
        ``CostModel.decode_token_latency`` predictions stop leaning on the
        training-step wall scale alone."""
        return self.decode_seconds / max(self.decode_tokens, 1)


class PEFTEngine:
    def __init__(
        self,
        gen: ModelGenerator,
        plan: ExecutionPlan,
        lr: float = 1e-4,
        aux_coef: float = 1e-3,
    ):
        self.gen = gen
        self.model: Model = gen.model
        self.plan = plan
        self.lr = lr
        self.aux_coef = aux_coef
        self.backbone = gen.init_backbone()
        assert gen.registered is not None, "register_tasks() first"
        self.reg: RegisteredTasks = gen.registered
        self._check_alignment()
        self._steps: Dict[Tuple, Callable] = {}   # hTask signature -> step
        self.cache_hits = 0
        self.cache_misses = 0
        self._adapter_sig = self._adapter_shape_sig()
        self._lr_scales = self._build_lr_scales()
        self._slot_steps = self._fresh_slot_steps()
        self._member_ids = self._build_member_ids()
        # task-aware decode pool (co-serving data plane); fns are compiled
        # lazily and invalidated with the training step cache (adapter-stack
        # shape changes), NOT on churn — the slot-stable decode contract
        self._decode_pool: Optional[Dict[str, Any]] = None
        self._decode_geom: Optional[Tuple] = None  # (rows, max_len, cap, prefix)
        self._decode_fns: Dict[Any, Callable] = {}
        self.decode_pool_gen = 0  # bumps when the pool is (re)allocated

    # ------------------------------------------------------------------

    def _check_alignment(self) -> None:
        plan_ids = [t.task_id for t in self.plan.tasks]
        reg_ids = [t.task_id for t in self.reg.tasks]
        assert plan_ids == reg_ids, (
            f"plan/registry task order mismatch: {plan_ids} vs {reg_ids}")

    def _adapter_shape_sig(self) -> Tuple:
        flat, _ = jax.tree_util.tree_flatten_with_path(self.reg.adapter_params)
        return tuple((jax.tree_util.keystr(p), tuple(l.shape), str(l.dtype))
                     for p, l in flat)

    def _fresh_slot_steps(self) -> Dict[str, jax.Array]:
        mta = self.reg.mta
        return {kind: jax.device_put(np.zeros((mta.kind_capacity[kind],), np.float32))
                for kind in mta.kind_tasks}

    def _build_member_ids(self) -> Dict[int, jax.Array]:
        """Per-hTask device-resident GLOBAL member index vectors (for the
        eager local→global loss scatter).  Built at (re-)plan time so the
        guarded iteration loop never implicitly transfers the indices."""
        return {i: jax.device_put(np.asarray(h.task_ids, np.int32))
                for i, h in enumerate(self.plan.htasks)}

    # ------------------------------------------------------------------

    def _broadcast_slots(self, vecs: Dict[str, Any]) -> Any:
        """Expand per-kind slot vectors [capacity] into a pytree aligned with
        the adapter params, each leaf reshaped to broadcast along the leaf's
        task axis.  Works on numpy constants and on traced arrays.  Leaves a
        method declares shared (no task axis) get a scalar 0.0 — as a mask
        or lr-scale that freezes them, which is exactly the optimizer hint
        the PEFTMethod protocol promises for shared frozen params."""
        mta = self.reg.mta
        depths = _group_depths(self.gen.cfg)
        params = self.reg.adapter_params

        def walk(tree: Any, depth: int, kind: Optional[str] = None, name=None):
            if not isinstance(tree, dict):
                if kind is None or tree is None or kind not in vecs:
                    return None
                if name is not None and shared_leaf(kind, name):
                    return jnp.zeros((), jnp.float32)  # frozen shared leaf
                v = vecs[kind]
                shape = [1] * tree.ndim
                shape[depth] = v.shape[0]
                return jnp.reshape(jnp.asarray(v), shape)
            out = {}
            for k, sub in tree.items():
                nk = k if k in mta.kind_tasks else kind
                out[k] = walk(sub, depth, nk, k)
            return out

        if "" in depths:
            return walk(params, depths[""])
        return {gk: walk(params.get(gk, {}), d) for gk, d in depths.items()}

    def _build_lr_scales(self):
        """Per-slot lr multipliers broadcast along each leaf's task axis."""
        mta = self.reg.mta
        base = self.lr
        vecs = {
            kind: mta.slot_values(kind, {i: mta.task_cfgs[i].lr for i in ids},
                                  fill=base) / base
            for kind, ids in mta.kind_tasks.items()
        }
        return self._broadcast_slots(vecs)

    # ------------------------------------------------------------------
    # Task churn: incremental re-plan (§3.2 online path)

    def attach_tasks(self, new_tasks: Sequence, plan: ExecutionPlan) -> None:
        """Hot-attach tenants: register (fresh-init adapters, zero moments at
        a free slot) and swap to ``plan``.  Compiled steps for buckets whose
        signature is unchanged are reused without retracing."""
        old_reg = self.reg
        self.reg = self.gen.register_tasks(new_tasks)
        self._after_rebuild(old_reg, plan)

    def detach_tasks(self, task_ids: Sequence[str], plan: ExecutionPlan,
                     compact: bool = False) -> None:
        """Detach tenants; their slots are freed for reuse.  ``compact=True``
        re-packs the stacks densely (physically freeing the departed
        tenants' adapter + moment memory) at the cost of a full recompile."""
        old_reg = self.reg
        self.reg = self.gen.deregister_tasks(task_ids)
        if compact:
            self.reg = self.gen.compact()
        self._after_rebuild(old_reg, plan)

    def _after_rebuild(self, old_reg: RegisteredTasks, plan: ExecutionPlan) -> None:
        self.plan = plan
        self._check_alignment()
        new_sig = self._adapter_shape_sig()
        if new_sig != self._adapter_sig:
            self._steps.clear()  # stack shapes changed: every step is stale
            self._decode_fns.clear()  # decode steps close over the stacks too
            self._adapter_sig = new_sig
        self._lr_scales = self._build_lr_scales()
        self._slot_steps = self._carry_slot_steps(old_reg)
        self._member_ids = self._build_member_ids()

    def _carry_slot_steps(self, old_reg: RegisteredTasks) -> Dict[str, jax.Array]:
        """Carry surviving tasks' per-slot update counts across a rebuild."""
        old_vecs = {k: np.asarray(v) for k, v in self._slot_steps.items()}
        old_ids = {t.task_id: i for i, t in enumerate(old_reg.tasks)}
        mta = self.reg.mta
        out = {}
        for kind, ids in mta.kind_tasks.items():
            vec = np.zeros((mta.kind_capacity[kind],), np.float32)
            for i in ids:
                oi = old_ids.get(self.reg.tasks[i].task_id)
                if oi is None or old_reg.tasks[oi].adapter.kind != kind:
                    continue
                old_vec = old_vecs.get(kind)
                if old_vec is None:
                    continue
                vec[int(mta.task_slot[i])] = old_vec[int(old_reg.mta.task_slot[oi])]
            out[kind] = jax.device_put(vec)
        return out

    # ------------------------------------------------------------------

    def step_signature(self, htask_idx: int) -> Tuple:
        """Canonical compiled-step identity — free of global task indices.

        Two hTasks (possibly from different plans / different tenant
        censuses) with equal signatures lower to the identical jitted
        computation, so the compiled step is shared.  The active kernel
        impl is part of the identity: jitted steps bake in whichever tier
        (``xla`` / ``pallas`` / ``pallas_interpret``) was live at trace
        time, so flipping ``kops.set_impl`` must miss the cache rather
        than silently reuse a step compiled for the other tier."""
        from repro.kernels import ops as kops

        h = self.plan.htasks[htask_idx]
        seg = self.plan.segments_for(htask_idx)
        mta = self.reg.mta
        row_sig = tuple((mta.task_cfgs[t].kind, int(mta.task_slot[t]))
                        for t in seg.row_task)
        mem_sig = tuple(
            (mta.task_cfgs[t].kind, int(mta.task_slot[t]),
             mta.task_cfgs[t].rank, float(mta.task_cfgs[t].scale),
             float(mta.task_cfgs[t].lr), tuple(sorted(mta.task_cfgs[t].targets)))
            for t in h.task_ids)
        return (kops.get_impl(), h.rows, h.row_len, row_sig, mem_sig,
                self._adapter_sig)

    def _make_step(self, htask_idx: int) -> Callable:
        h = self.plan.htasks[htask_idx]
        segments = self.plan.segments_for(htask_idx)
        local_seg = segments.relabel(h.task_ids)
        ctxf = self.reg.mta.ctx_factory(segments)
        model = self.model
        aux_coef = self.aux_coef
        lr = self.lr
        lr_scales = self._lr_scales
        mta = self.reg.mta
        # member masks: 1.0 at member slots, 0 elsewhere — confines update,
        # moments and step counts to the tasks present in this micro-batch
        member_slots: Dict[str, set] = {}
        for t in h.task_ids:
            member_slots.setdefault(mta.task_cfgs[t].kind, set()).add(
                int(mta.task_slot[t]))
        mask_vecs = {
            kind: np.asarray([1.0 if s in member_slots.get(kind, ()) else 0.0
                              for s in range(mta.kind_capacity[kind])], np.float32)
            for kind in mta.kind_tasks
        }
        masks = self._broadcast_slots(mask_vecs)

        # Named scopes label the step's phases in the device trace (HLO
        # op_name metadata only: the compiled program is otherwise the
        # same).  Backward ops carry ``transpose(`` in their op_name, the
        # forward recomputed inside the backward included.
        def loss_fn(adapters, backbone, batch):
            with jax.named_scope("train.forward"):
                out = model.forward(backbone, batch, adapters=adapters,
                                    ctx_factory=ctxf)
                pt = local_seg.per_task_loss(out["per_token_loss"],
                                             batch["loss_mask"])
                loss = pt.sum()
                for k, v in out["aux"].items():
                    if k == "moe_load_balance":
                        loss = loss + aux_coef * v
            return loss, pt

        def step(backbone, adapters, opt_state, slot_steps, batch, member_ids, acc):
            # ``member_ids`` (the members' GLOBAL task indices) and ``acc``
            # (iteration accumulators) are traced inputs, NOT baked
            # constants — the compiled step stays re-plan-agnostic while the
            # local→global loss scatter still runs on device inside the jit.
            (loss, pt), grads = jax.value_and_grad(
                loss_fn, has_aux=True, allow_int=True
            )(adapters, backbone, batch)
            prev_opt = opt_state
            with jax.named_scope("train.optimizer"):
                finite = jnp.isfinite(loss)
                # NaN guard composes with member masking: a diverging step
                # keeps non-members untouched by construction and reverts
                # members.
                counts = {k: jnp.where(finite, v + mask_vecs[k], v)
                          for k, v in slot_steps.items()}
                step_counts = self._broadcast_slots(counts)
                updates, opt_state = adamw_update(
                    grads, opt_state, adapters, lr=lr, lr_scales=lr_scales,
                    step_counts=step_counts,
                )

                def guard_update(u, mk):
                    if u is None:
                        return None
                    m = 1.0 if mk is None else mk.astype(u.dtype)
                    return jnp.where(finite, u * m, jnp.zeros_like(u))

                def guard_moment(new, old, mk):
                    if new is None:
                        return None
                    keep = finite if mk is None else (finite & (mk > 0))
                    return jnp.where(keep, new, old)

                updates = jax.tree.map(guard_update, updates, masks,
                                       is_leaf=lambda x: x is None)
                opt_state = opt_state._replace(
                    m=jax.tree.map(guard_moment, opt_state.m, prev_opt.m, masks,
                                   is_leaf=lambda x: x is None),
                    v=jax.tree.map(guard_moment, opt_state.v, prev_opt.v, masks,
                                   is_leaf=lambda x: x is None),
                )
                adapters = apply_updates(adapters, updates)
            total, pt_acc = acc
            total = total + loss
            pt_acc = pt_acc.at[member_ids].add(pt)
            return adapters, opt_state, counts, (total, pt_acc)

        return jax.jit(step, donate_argnums=(1, 2, 3, 6))

    def _step_for(self, htask_idx: int) -> Callable:
        key = self.step_signature(htask_idx)
        if key not in self._steps:
            self._steps[key] = self._make_step(htask_idx)
            self.cache_misses += 1
        else:
            self.cache_hits += 1
        return self._steps[key]

    # ------------------------------------------------------------------

    def _schedule(self, n_micro: Optional[int]) -> List[int]:
        """hTask launch order for one iteration (template order).

        ``n_micro=None`` follows the planner's template verbatim.  An
        explicit ``n_micro`` is honored per bucket: each bucket runs exactly
        ``n_micro`` micro-steps — template entries beyond that are
        truncated, buckets the template under-covers are repeated.
        """
        buckets = self.plan.template.buckets
        order = [m.bucket for m in self.plan.template.micro_order]
        if n_micro is not None:
            counts = [0] * len(buckets)
            kept: List[int] = []
            for b in order:
                if counts[b] < n_micro:
                    counts[b] += 1
                    kept.append(b)
            for b in range(len(buckets)):
                kept.extend([b] * (n_micro - counts[b]))
            order = kept
        return [hid for b in order for hid in buckets[b].htask_ids]

    # ------------------------------------------------------------------
    # Task-aware decode pool (SLO co-serving data plane)

    def decode_prefix_reserve(self) -> int:
        from repro.launch.steps import decode_prefix_reserve

        return decode_prefix_reserve(self.reg.mta)

    def ensure_decode_pool(self, rows: int, max_len: int,
                           max_new_cap: int) -> Dict[str, Any]:
        """Allocate (or re-allocate on geometry change) the fused decode
        pool.  A re-allocation bumps ``decode_pool_gen`` — in-flight rows
        are lost and the owning scheduler must re-bind its requests."""
        pres = self.decode_prefix_reserve()
        geom = (rows, max_len, max_new_cap, pres)
        if self._decode_pool is None or self._decode_geom != geom:
            from repro.launch.steps import init_decode_pool

            self._decode_pool = init_decode_pool(
                self.model, rows, max_len, max_new_cap, prefix_reserve=pres)
            self._decode_geom = geom
            self._decode_fns.clear()
            self.decode_pool_gen += 1
        return self._decode_pool

    def decode_row_ctx(self, row_task: Sequence[int]):
        """(row_slots, scales) device-feedable dicts for a row->GLOBAL-task
        map (-1 = unbound row) under the CURRENT registration."""
        mta = self.reg.mta
        slots = {k: jnp.asarray(v)
                 for k, v in mta.decode_row_slots(row_task).items()}
        scales = {k: jnp.asarray(mta.scales(k)) for k in mta.kind_tasks}
        return slots, scales

    def decode_micro_ready(self) -> bool:
        """True once the fused decode micro-step is compiled — latency
        samples taken before this are trace/compile transients and must not
        enter the SLO p50/p99 window."""
        from repro.kernels import ops as kops

        return (kops.get_impl(), "micro") in self._decode_fns

    def _decode_fn(self, key, builder) -> Callable:
        # decode fns bake in the trace-time kernel impl too (see
        # step_signature) — key them by tier so impl flips retrace
        from repro.kernels import ops as kops

        key = (kops.get_impl(), key)
        fn = self._decode_fns.get(key)
        if fn is None:
            fn = self._decode_fns[key] = builder()
        return fn

    def dispatch_decode_micro(self, row_slots, scales) -> None:
        """Enqueue ONE fused decode token for the pool (async dispatch —
        no host sync; interleavable between training micro-steps)."""
        from repro.launch.steps import build_decode_micro_step

        fn = self._decode_fn(
            "micro", lambda: build_decode_micro_step(
                self.model, self.reg.mta, self._decode_geom[3]))
        with span("decode.micro_step", track="engine"):
            self._decode_pool = fn(self.backbone, self.reg.adapter_params,
                                   self._decode_pool, row_slots, scales)

    def dispatch_decode_bind(self, row: int, tokens: np.ndarray, length: int,
                             row_slots, scales, max_new: int,
                             sampling=None) -> None:
        """Bind a request to pool row ``row``: single-row prefill + prefix
        KV fold + scatter (async).  ``tokens`` is [1, Lp] (a fixed prompt
        bucket: one compiled bind per Lp).  ``sampling`` carries the
        request's {temp, top_k, top_p, rng} [1]-vectors (greedy default)."""
        self.dispatch_decode_bind_batched(
            np.asarray([row], np.int32), np.asarray(tokens, np.int32),
            np.asarray([length], np.int32), row_slots, scales,
            np.asarray([max_new], np.int32), sampling)

    def dispatch_decode_bind_batched(self, rows, tokens, lengths, row_slots,
                                     scales, max_new, sampling=None) -> None:
        """Bind ``R`` requests in ONE batched-prefill launch (async).
        ``tokens`` is [R, Lp] (all requests of one prompt bucket); one
        compiled bind serves every (R, Lp) pair.  ``sampling`` carries the
        per-request {temp, top_k, top_p [R], rng [R, 2]} sampling state."""
        from repro.launch.steps import build_decode_batched_bind_step, greedy_sampling

        R, Lp = int(tokens.shape[0]), int(tokens.shape[1])
        fn = self._decode_fn(
            ("bind", R, Lp),
            lambda: build_decode_batched_bind_step(
                self.model, self.reg.mta, self._decode_geom[1],
                self._decode_geom[3]))
        if sampling is None:
            sampling = greedy_sampling(R)
        else:
            sampling = {
                "temp": jnp.asarray(sampling["temp"], jnp.float32),
                "top_k": jnp.asarray(sampling["top_k"], jnp.int32),
                "top_p": jnp.asarray(sampling["top_p"], jnp.float32),
                "rng": jnp.asarray(sampling["rng"], jnp.uint32),
            }
        with span("decode.bind", track="engine", args={"rows": R, "bucket": Lp}):
            self._decode_pool = fn(
                self.backbone, self.reg.adapter_params, self._decode_pool,
                jnp.asarray(rows, jnp.int32), jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lengths, jnp.int32), row_slots, scales,
                jnp.asarray(max_new, jnp.int32), sampling)

    def decode_accounting(self) -> Dict[str, np.ndarray]:
        """The per-iteration host sync of the decode pool: small counters
        only (generated counts, active flags, context lengths)."""
        p = self._decode_pool
        with span("decode.accounting_sync", track="engine"):
            got = jax.device_get({"n_out": p["n_out"], "active": p["active"],
                                  "pos": p["state"]["pos"]})
        return {k: np.asarray(v) for k, v in got.items()}

    def decode_outputs(self, row: int) -> np.ndarray:
        """Generated token buffer of one pool row (request completion)."""
        return np.asarray(jax.device_get(self._decode_pool["out"][row]))

    # ------------------------------------------------------------------

    def run_iteration(
        self, loaders: Dict[int, Iterator], n_micro: Optional[int] = None,
        interleave: Optional[Callable[[], None]] = None,
    ) -> StepMetrics:
        """One training iteration: all buckets, template order, C micro each.

        Stall-free dispatch: loss and per-task metrics live in
        device-resident accumulators, so micro-steps enqueue back-to-back
        with NO host synchronization in the loop — the only device→host
        transfer is one explicit ``jax.device_get`` of the accumulated
        metrics at the end of the iteration.  Host→device batch transfer is
        double-buffered: the next micro-batch's ``device_put`` DMA is in
        flight while the current step computes.  The local→global per-task
        loss scatter uses the pre-staged device index vectors, so it adds no
        transfer either.

        ``interleave`` (token-level co-serving): a callable invoked after
        every training micro-step's dispatch.  It may enqueue decode
        micro-steps (``dispatch_decode_micro``) — because dispatch is
        asynchronous, this interleaves inference tokens INTO the training
        iteration's device queue without stalling either stream.

        Observability: the loop is span-instrumented (``engine.iteration``
        / ``engine.prefetch`` / ``engine.micro_step`` / ``engine.sync`` on
        the ``engine`` track).  With tracing OFF — the default — every span
        site is a shared no-op context manager: no allocation, no extra
        ``device_get``, so the stall-free transfer discipline above is
        untouched (proven by the transfer-guard test's device_get census).
        """
        from repro.launch.steps import prefetch_to_device

        with span("engine.iteration", track="engine"):
            t0 = time.perf_counter()
            schedule = self._schedule(n_micro)
            # device_put (not jnp.zeros) so accumulator init is an explicit
            # transfer — the whole loop stays clean under transfer_guard.
            # per-task accumulator sized to the total slot CAPACITY (not the
            # live task count): capacity only changes when the adapter stacks
            # are reshaped — exactly when the step cache is cleared — so
            # reused steps never retrace on a censal shift; sliced to live
            # tasks on host
            n_acc = max(len(self.plan.tasks),
                        sum(self.reg.mta.kind_capacity.values()))
            acc = (jax.device_put(np.float32(0.0)),
                   jax.device_put(np.zeros((n_acc,), np.float32)))
            tokens = eff = 0
            batches = prefetch_to_device(next(loaders[h]) for h in schedule)
            for hid in schedule:
                try:
                    with span("engine.prefetch", track="engine"):
                        batch = next(batches)
                except StopIteration:
                    break
                step = self._step_for(hid)
                with span("engine.micro_step", track="engine"):
                    (self.reg.adapter_params, self.reg.opt_state,
                     self._slot_steps, acc) = step(
                        self.backbone, self.reg.adapter_params,
                        self.reg.opt_state, self._slot_steps, batch,
                        self._member_ids[hid], acc,
                    )
                h = self.plan.htasks[hid]
                tokens += h.tokens
                eff += h.effective_tokens
                if interleave is not None:
                    interleave()
            # The iteration's single host sync: one explicit transfer of the
            # device accumulators (blocks until the whole iteration retires).
            with span("engine.sync", track="engine"):
                loss_h, pt_h = jax.device_get(acc)
            dt = time.perf_counter() - t0
        pt_h = np.asarray(pt_h, np.float64)[: len(self.plan.tasks)]
        return StepMetrics(float(loss_h), pt_h, tokens, eff, dt)

    def throughput(self, metrics: StepMetrics) -> Dict[str, float]:
        return {
            "tokens_per_s": metrics.tokens / max(metrics.wall_seconds, 1e-9),
            "effective_tokens_per_s": metrics.effective_tokens / max(metrics.wall_seconds, 1e-9),
        }
