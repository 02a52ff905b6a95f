"""Step builders + abstract input specs for launcher, dry-run and benchmarks.

``input_specs`` returns weak-type-correct ``ShapeDtypeStruct`` stand-ins for
every model input (tokens, labels, masks, mrope ids, audio frames, decode
state) — shardable, zero allocation.  ``build_*_step`` return the exact
callables the production system jits: the multi-task PEFT train step
(adapter-grad backward + AdamW), the prefill step, and the serve step (one
token over the KV/SSM state).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, ShapeSpec
from repro.distributed.sharding import ShardingRules, activate_rules, logical_to_spec
from repro.models.layers import abstract, is_spec_leaf, spec_logical_axes
from repro.models.transformer import Model
from repro.obs.tracing import span
from repro.peft.adapters import LORA
from repro.peft.methods import AdapterConfig
from repro.peft.multitask import MultiTaskAdapters, TaskSegments
from repro.train.optimizer import AdamWState, adamw_init, adamw_update, apply_updates

# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------

BATCH_AXES: Dict[str, Tuple] = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "loss_mask": ("batch", "seq"),
    "segment_ids": ("batch", "seq"),
    "positions": ("batch", "seq"),
    "reset": ("batch", "seq"),
    "mrope_positions": (None, "batch", "seq"),
    "audio_embed": ("batch", None, None),
}


def batch_specs(cfg: ArchConfig, shape: ShapeSpec, with_labels: bool = True,
                with_positions: bool = False) -> Dict[str, jax.ShapeDtypeStruct]:
    B, S = shape.global_batch, shape.seq_len
    role = shape.kind
    if role == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    specs = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if with_positions:
        # striped-CP layout: global positions travel with the data
        specs["positions"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if with_labels and role == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["loss_mask"] = jax.ShapeDtypeStruct((B, S), jnp.float32)
    if cfg.mrope:
        specs["mrope_positions"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
    if cfg.family == "audio":
        specs["audio_embed"] = jax.ShapeDtypeStruct(
            (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16
        )
    return specs


def batch_shardings(specs: Dict[str, Any], mesh: Mesh, rules: ShardingRules):
    r = rules.mesh_axes(mesh)
    return {
        k: NamedSharding(mesh, logical_to_spec(BATCH_AXES[k], r))
        for k, v in specs.items()
    }


# ---------------------------------------------------------------------------
# Param / adapter / state shardings
# ---------------------------------------------------------------------------


def tree_shardings(axes_tree: Any, mesh: Mesh, rules: ShardingRules):
    r = rules.mesh_axes(mesh)
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, r)),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x),
    )


def param_shardings(model: Model, mesh: Mesh, rules: ShardingRules):
    return tree_shardings(spec_logical_axes(model.spec()), mesh, rules)


def adapter_shardings(mta: MultiTaskAdapters, mesh: Mesh, rules: ShardingRules):
    return tree_shardings(spec_logical_axes(mta.spec()), mesh, rules)


def opt_shardings(opt_abstract: AdamWState, mesh: Mesh,
                  mta: Optional[MultiTaskAdapters] = None,
                  cfg: Optional[ArchConfig] = None,
                  rules: Optional[ShardingRules] = None):
    """AdamW moment shardings.

    With ``mta``/``cfg`` given, moments shard along each leaf's adapter-stack
    TASK axis (logical axis ``adapter_tasks`` -> DP ranks): per-tenant
    optimizer state is the dominant multi-tenant memory term and scales with
    tenant count, so slicing it across data-parallel ranks keeps per-chip
    moment bytes flat as tenants grow.  Leaves whose task dim doesn't divide
    the mesh axis — and the step scalar — stay replicated.  Without ``mta``
    the legacy fully-replicated layout is returned.
    """
    rep = NamedSharding(mesh, P())
    if mta is None or cfg is None:
        return jax.tree.map(lambda _: rep, opt_abstract)
    from repro.core.registry import _group_depths
    from repro.distributed.sharding import divisible

    r = (rules or ShardingRules()).mesh_axes(mesh)
    target = r.lookup("adapter_tasks")
    depths = _group_depths(cfg)

    def leaf_sharding(leaf, depth):
        nd = getattr(leaf, "ndim", 0)
        if (target is None or nd <= depth
                or not divisible(leaf.shape[depth], mesh, target)):
            return rep
        axes = [None] * nd
        axes[depth] = "adapter_tasks"
        return NamedSharding(mesh, logical_to_spec(axes, r))

    def walk(tree, depth, kind=None, name=None):
        if not isinstance(tree, dict):
            if tree is None:
                return None  # non-float leaf: stays an empty pytree node
            if kind is None:
                return rep
            from repro.peft.methods import shared_leaf

            if name is not None and shared_leaf(kind, name):
                return rep  # no task axis to slice: replicate
            return leaf_sharding(tree, depth)
        out = {}
        for k, v in tree.items():
            nk = k if k in mta.kind_tasks else kind
            out[k] = walk(v, depth, nk, k)
        return out

    def moments(tree):
        if "" in depths:
            return walk(tree, depths[""])
        return {gk: walk(tree.get(gk, {}), d) for gk, d in depths.items()}

    return AdamWState(rep, moments(opt_abstract.m), moments(opt_abstract.v))


def _state_axes(cfg: ArchConfig, state: Any) -> Any:
    """Logical axes tree matching a decode-state pytree."""

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        nd = node.ndim if hasattr(node, "ndim") else 0
        if path[-1] in ("pos", "t", "lo") or nd == 0:
            # per-row decode-pool counters shard with the cache batch
            return ("cache_batch",)[:nd]
        if path[0] == "kv" or path[-1] in ("cross_k", "cross_v"):
            return ("layers", "cache_batch", "cache_seq", "kv_heads", "head_dim")[:nd] if path[0] == "kv" else (
                "layers", "cache_batch", None, "heads", "head_dim")[:nd]
        if path[0] == "mamba":
            if path[-1] == "h":  # [ns, per, B, nh, st, hd]
                return ("layers", "layers", "cache_batch", "ssm_heads", None, None)[:nd]
            return ("layers", "layers", "cache_batch", None, "ssm_inner")[:nd]
        if path[0] == "mlstm":  # [ns, per, B, nh, dk, dv]
            return ("layers", "layers", "cache_batch", None, "ssm_state", None)[:nd]
        if path[0] == "slstm":  # [ns, B, nh, hd]
            return ("layers", "cache_batch", None, None)[:nd]
        return tuple([None] * nd)

    return walk(state, ())


def decode_state_specs(model: Model, shape: ShapeSpec) -> Any:
    """Abstract decode state via eval_shape (no allocation)."""
    cfg = model.cfg

    def init():
        return model.init_decode_state(None, shape.global_batch, shape.seq_len)

    return jax.eval_shape(init)


def decode_state_shardings(model: Model, shape: ShapeSpec, mesh: Mesh, rules: ShardingRules):
    state = decode_state_specs(model, shape)
    axes = _state_axes(model.cfg, state)
    r = rules.mesh_axes(mesh)
    return jax.tree.map(
        lambda a: NamedSharding(mesh, logical_to_spec(a, r)),
        axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Multi-task setup for production cells
# ---------------------------------------------------------------------------


def dryrun_tasks(cfg: ArchConfig, shape: ShapeSpec, n_tasks: int = 8, rank: int = 16):
    """The multi-tenant task set a production train cell carries."""
    n_tasks = min(n_tasks, shape.global_batch)
    cfgs = [AdapterConfig(LORA, rank=rank) for _ in range(n_tasks)]
    mta = MultiTaskAdapters(cfg, cfgs)
    rows = shape.global_batch // n_tasks
    seg = TaskSegments.contiguous([rows] * n_tasks)
    # remainder rows go to the last task
    if rows * n_tasks != shape.global_batch:
        extra = shape.global_batch - rows * n_tasks
        seg = TaskSegments(seg.row_task + (n_tasks - 1,) * extra, n_tasks)
    return mta, seg


# ---------------------------------------------------------------------------
# Host→device transfer (stall-free dispatch discipline)
# ---------------------------------------------------------------------------


def device_put_batch(batch: Dict[str, Any], shardings: Optional[Dict] = None):
    """EXPLICIT async host→device transfer of one loader batch.

    ``jax.device_put`` on host numpy returns immediately with the DMA in
    flight, so a caller can enqueue the *next* batch's transfer while the
    current step computes (double-buffering).  Using the explicit API also
    keeps the train loop clean under ``jax.transfer_guard("disallow")`` —
    no implicit np↔device conversions serialize dispatch.
    """
    if shardings is None:
        return {k: jax.device_put(v) for k, v in batch.items()}
    return {k: jax.device_put(v, shardings.get(k)) for k, v in batch.items()}


def prefetch_to_device(it, size: int = 2, shardings: Optional[Dict] = None):
    """Wrap a host batch iterator with a ``size``-deep device prefetch queue.

    Keeps ``size`` batches' H2D DMAs in flight ahead of the consumer, so the
    device never idles waiting on the host loader (MuxServe-style stall-free
    dispatch).  Yields batches in order; safe for finite or infinite
    iterators.
    """
    from collections import deque

    it = iter(it)
    buf: deque = deque()

    def fill() -> None:
        # the host fill and the copy are spans of their own, so a device
        # idle gap goes to the one the host was in
        while len(buf) < size:
            try:
                with span("engine.prefetch.fill", track="engine"):
                    host = next(it)
            except StopIteration:
                return
            with span("engine.prefetch.h2d", track="engine"):
                buf.append(device_put_batch(host, shardings))

    fill()
    while buf:
        out = buf.popleft()
        fill()
        yield out


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def build_train_step(model: Model, mta: MultiTaskAdapters, segments: TaskSegments,
                     lr: float = 1e-4, aux_coef: float = 1e-3):
    ctxf = mta.ctx_factory(segments)

    def train_step(backbone, adapters, opt_state, batch):
        # phase scopes as in ``PEFTEngine._make_step``
        def loss_fn(ad):
            with jax.named_scope("train.forward"):
                out = model.forward(backbone, batch, adapters=ad, ctx_factory=ctxf)
                pt = segments.per_task_loss(out["per_token_loss"], batch["loss_mask"])
                loss = pt.sum()
                for k, v in out["aux"].items():
                    if k == "moe_load_balance":
                        loss = loss + aux_coef * v
            return loss, pt

        (loss, pt), grads = jax.value_and_grad(loss_fn, has_aux=True, allow_int=True)(adapters)
        with jax.named_scope("train.optimizer"):
            updates, opt_state = adamw_update(grads, opt_state, adapters, lr=lr)
            adapters = apply_updates(adapters, updates)
        return adapters, opt_state, loss, pt

    return train_step


def build_prefill_step(model: Model):
    def prefill_step(backbone, batch):
        out = model.forward(backbone, batch, return_logits=True)
        return out["logits"]

    return prefill_step


def build_serve_step(model: Model):
    def serve_step(backbone, state, tokens):
        return model.decode_step(backbone, state, tokens)

    return serve_step


# ---------------------------------------------------------------------------
# Task-aware decode pool (SLO co-serving data plane)
# ---------------------------------------------------------------------------
#
# The pool is a fixed-geometry fused decode batch: ``rows`` independent
# inference requests share ONE compiled micro-step, each row bound to a
# tenant's adapter slot (-1 = idle).  Row->task routing enters the jitted
# steps as TRACED slot vectors (``ctx_factory_from_slots``), so binding and
# unbinding requests — and tenant churn that renumbers tasks — never
# retraces; only adapter-stack shape changes do (the same invalidation rule
# as the training step cache).  The whole generation loop stays on device:
# sampling (temperature / top-k / top-p, per-row PRNG keys — all traced
# pool state, so per-request params never retrace) feeds back internally,
# tokens accumulate in the ``out`` buffer, and the host syncs accounting
# once per iteration.  ``temp <= 0`` rows reduce EXACTLY to greedy argmax.


def decode_prefix_reserve(mta: MultiTaskAdapters) -> int:
    """Static prefix region of the pool's KV cache: the widest soft-prompt
    row count any resident kind can fold in (rows are owned exclusively, so
    the max — not the sum — bounds the region)."""
    from repro.peft.methods import get_method

    return max((mta.kind_rank[k] for k in mta.kind_tasks
                if get_method(k).uses_attention_prefix), default=0)


def init_decode_pool(model: Model, rows: int, max_len: int, max_new_cap: int,
                     prefix_reserve: int = 0, cache_dtype=jnp.bfloat16):
    """Allocate the fused decode pool (all rows idle, greedy sampling)."""
    state = model.init_decode_state(None, rows, max_len,
                                    cache_dtype=cache_dtype,
                                    prefix_reserve=prefix_reserve,
                                    per_row=True)
    def z():  # distinct buffers: the pool is donated through jitted steps
        return jnp.zeros((rows,), jnp.int32)

    return {
        "state": state,
        "cur": z(),                                 # next input token per row
        "out": jnp.zeros((rows, max_new_cap), jnp.int32),  # generated tokens
        "n_out": z(),                               # generated count per row
        "active": z(),                              # 1 while generating
        "max_new": z(),                             # per-row generation target
        # per-row sampling state (traced: params change without retracing)
        "temp": jnp.zeros((rows,), jnp.float32),    # 0 => greedy
        "top_k": jnp.zeros((rows,), jnp.int32),     # 0 => off
        "top_p": jnp.ones((rows,), jnp.float32),    # 1 => off
        "rng": jnp.zeros((rows, 2), jnp.uint32),    # per-row PRNG key
    }


def greedy_sampling(rows: int) -> Dict[str, jax.Array]:
    """Per-row sampling params that reduce exactly to argmax."""
    return {
        "temp": jnp.zeros((rows,), jnp.float32),
        "top_k": jnp.zeros((rows,), jnp.int32),
        "top_p": jnp.ones((rows,), jnp.float32),
        "rng": jnp.zeros((rows, 2), jnp.uint32),
    }


def sample_tokens(logits: jax.Array, temp: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, rng: jax.Array):
    """On-device per-row sampling over ``[B, V]`` logits.

    ``temp[b] <= 0`` makes row ``b`` EXACTLY greedy (argmax — no RNG draw
    enters the token).  ``top_k <= 0`` and ``top_p >= 1`` disable those
    filters.  ``rng`` is ``[B, 2]`` uint32 per-row PRNG key data; returns
    ``(tokens [B] int32, advanced rng [B, 2])`` so the caller threads the
    key through the pool state.
    """
    # jax.named_scope: the label survives into the lowered HLO, so device
    # profiles (jax.profiler.trace) show the sampling phase as its own
    # region under the host-side decode spans (repro.obs.tracing)
    with jax.named_scope("decode.sample"):
        return _sample_tokens_impl(logits, temp, top_k, top_p, rng)


def _sample_tokens_impl(logits, temp, top_k, top_p, rng):
    B, V = logits.shape
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    scaled = lg / jnp.maximum(temp, 1e-6)[:, None]
    # top-k: keep logits >= the k-th largest of the row
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    keep = (top_k[:, None] <= 0) | (scaled >= kth)
    # top-p (nucleus): smallest prefix of descending probs reaching top_p;
    # ties at the cutoff probability are all kept
    probs = jax.nn.softmax(jnp.where(keep, scaled, -1e30), axis=-1)
    ps = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(ps, axis=-1)
    in_nucleus = (cum - ps) < top_p[:, None]
    cutoff = jnp.min(jnp.where(in_nucleus, ps, jnp.inf), axis=-1)
    keep &= (top_p[:, None] >= 1.0) | (probs >= cutoff[:, None])
    filtered = jnp.where(keep, scaled, -1e30)
    splits = jax.vmap(lambda kk: jax.random.split(kk, 2))(rng)  # [B, 2, 2]
    sampled = jax.vmap(jax.random.categorical)(splits[:, 1], filtered)
    nxt = jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)
    return nxt, splits[:, 0]


def build_decode_micro_step(model: Model, mta: MultiTaskAdapters,
                            prefix_reserve: int = 0):
    """One fused generation token for every active pool row (jitted).

    Feeds each row's ``cur`` token, samples the continuation with the row's
    traced sampling params (``temp``/``top_k``/``top_p``/``rng`` — greedy
    when ``temp <= 0``), advances only active rows.  Inactive rows still
    compute (static shapes) but their decode state is frozen — the cache
    rows they touch stay outside the valid window, so a later rebind sees a
    clean slate.
    """

    def decode_micro(backbone, adapters, pool, row_slots, scales):
        ctxf = mta.ctx_factory_from_slots(row_slots, scales)
        st = pool["state"]
        active = pool["active"] > 0
        with jax.named_scope("decode.step"):
            logits, new_st = model.decode_step(
                backbone, st, pool["cur"][:, None], adapters=adapters,
                ctx_factory=ctxf, prefix_reserve=prefix_reserve)
        nxt, rng2 = sample_tokens(logits[:, 0, :], pool["temp"],
                                  pool["top_k"], pool["top_p"], pool["rng"])
        B = pool["cur"].shape[0]
        rows = jnp.arange(B)
        widx = jnp.minimum(pool["n_out"], pool["out"].shape[1] - 1)
        out_buf = pool["out"].at[rows, widx].set(
            jnp.where(active, nxt, pool["out"][rows, widx]))
        n_out = pool["n_out"] + active.astype(jnp.int32)
        # freeze inactive rows' per-row counters (their cache writes land
        # outside the frozen window and are overwritten before re-exposure)
        new_st = dict(new_st)
        new_st["pos"] = jnp.where(active, new_st["pos"], st["pos"])
        return {
            "state": new_st,
            "cur": jnp.where(active, nxt, pool["cur"]),
            "out": out_buf,
            "n_out": n_out,
            "active": (active & (n_out < pool["max_new"])).astype(jnp.int32),
            "max_new": pool["max_new"],
            "temp": pool["temp"],
            "top_k": pool["top_k"],
            "top_p": pool["top_p"],
            # freeze inactive rows' keys too: replaying a bound request is
            # deterministic regardless of how long it sat in the pool
            "rng": jnp.where(active[:, None], rng2, pool["rng"]),
        }

    return jax.jit(decode_micro, donate_argnums=(2,))


def build_decode_batched_bind_step(model: Model, mta: MultiTaskAdapters,
                                   max_len: int, prefix_reserve: int = 0):
    """Bind ``R`` requests to pool rows in ONE launch (jitted): batched
    multi-row chunked PREFILL (``tokens [R, Lp]`` padded, per-row true
    ``lengths``) into a fresh ``R``-row cache, soft-prompt k/v rows folded
    into each row's reserved prefix region (right-aligned, per-row window
    ``lo``), first tokens sampled with each request's params, then all
    rows scattered into the pool.  ``rows``/slot routing/sampling are
    traced, so one compiled bind serves every (rows, tenants) combination
    of a ``(R, prompt-bucket)`` pair.
    """
    cfg = model.cfg
    from repro.peft.methods import get_method

    prefix_kinds = tuple(k for k in mta.kind_tasks
                         if get_method(k).uses_attention_prefix)
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()

    def bind_n(backbone, adapters, pool, rows, tokens, lengths, row_slots,
               scales, max_new, sampling):
        # tokens [R, Lp] (padded), lengths [R] true prompt lens, rows [R],
        # row_slots {kind: [R]}, max_new [R], sampling {temp/top_k/top_p
        # [R], rng [R, 2]}
        R = tokens.shape[0]
        ctxf = mta.ctx_factory_from_slots(row_slots, scales)
        st1 = model.init_decode_state(None, R, max_len,
                                      cache_dtype=pool["state"]["kv"]["k"].dtype,
                                      prefix_reserve=prefix_reserve,
                                      per_row=True)
        batch = {"tokens": tokens}
        if cfg.mrope:
            S = tokens.shape[1]
            batch["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (3, R, S))
        with jax.named_scope("decode.prefill"):
            logits, st1 = model.prefill(backbone, batch, st1,
                                        adapters=adapters, ctx_factory=ctxf,
                                        prefix_reserve=prefix_reserve,
                                        lengths=lengths)
        # fold soft-prompt rows into the reserved prefix region + window
        k1, v1 = st1["kv"]["k"], st1["kv"]["v"]
        lo_val = jnp.full((R,), prefix_reserve, jnp.int32)
        for kind in prefix_kinds if prefix_reserve else ():
            kspec = adapters.get(kind, {}).get("attn_prefix")
            if kspec is None:
                continue
            slot = row_slots[kind]                     # [R]
            has = slot >= 0
            pk = kspec["pk"][:, jnp.maximum(slot, 0)]  # [L, R, P, kv_dim]
            pv = kspec["pv"][:, jnp.maximum(slot, 0)]
            P = pk.shape[2]
            pk = pk.reshape(pk.shape[0], R, P, hkv, dh).astype(k1.dtype)
            pv = pv.reshape(pv.shape[0], R, P, hkv, dh).astype(v1.dtype)
            sl = slice(prefix_reserve - P, prefix_reserve)
            gate = has[None, :, None, None, None]
            k1 = k1.at[:, :, sl].set(jnp.where(gate, pk, k1[:, :, sl]))
            v1 = v1.at[:, :, sl].set(jnp.where(gate, pv, v1[:, :, sl]))
            lo_val = jnp.where(has, lo_val - P, lo_val)
        # first generated token: sampled at the last TRUE prompt position
        last = jnp.take_along_axis(
            logits.astype(jnp.float32),
            jnp.reshape(jnp.maximum(lengths - 1, 0), (R, 1, 1)), axis=1)
        first, rng1 = sample_tokens(last[:, 0], sampling["temp"],
                                    sampling["top_k"], sampling["top_p"],
                                    sampling["rng"])
        # scatter the bound rows into the pool
        ps = pool["state"]
        new_kv = {
            "k": ps["kv"]["k"].at[:, rows].set(k1),
            "v": ps["kv"]["v"].at[:, rows].set(v1),
        }
        new_state = dict(ps)
        new_state["kv"] = new_kv
        new_state["pos"] = ps["pos"].at[rows].set(st1["pos"])
        new_state["lo"] = ps["lo"].at[rows].set(lo_val)
        return {
            "state": new_state,
            "cur": pool["cur"].at[rows].set(first),
            "out": pool["out"].at[rows].set(0).at[rows, 0].set(first),
            "n_out": pool["n_out"].at[rows].set(1),
            "active": pool["active"].at[rows].set(
                (max_new > 1).astype(jnp.int32)),
            "max_new": pool["max_new"].at[rows].set(max_new),
            "temp": pool["temp"].at[rows].set(sampling["temp"]),
            "top_k": pool["top_k"].at[rows].set(sampling["top_k"]),
            "top_p": pool["top_p"].at[rows].set(sampling["top_p"]),
            "rng": pool["rng"].at[rows].set(rng1),
        }

    return jax.jit(bind_n, donate_argnums=(2,))


def build_decode_bind_step(model: Model, mta: MultiTaskAdapters,
                           max_len: int, prefix_reserve: int = 0):
    """Single-request bind: the ``R == 1`` case of
    :func:`build_decode_batched_bind_step` with the legacy scalar
    signature (``row []``, ``tokens [1, Lp]``, ``length []``).  Sampling
    params default to greedy when not given.
    """
    bind_n = build_decode_batched_bind_step(model, mta, max_len, prefix_reserve)

    def bind(backbone, adapters, pool, row, tokens, length, row_slots,
             scales, max_new, sampling=None):
        if sampling is None:
            sampling = greedy_sampling(1)
        return bind_n(
            backbone, adapters, pool,
            jnp.reshape(jnp.asarray(row, jnp.int32), (1,)), tokens,
            jnp.reshape(jnp.asarray(length, jnp.int32), (1,)), row_slots,
            scales, jnp.reshape(jnp.asarray(max_new, jnp.int32), (1,)),
            sampling)

    return bind
