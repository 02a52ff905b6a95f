"""Pipeline parallelism: collective-permute pipeline driven by the MuxTune
structured template (§3.4.1).

Realization: the classic JAX "collective pipeline" — stage-stacked params
live on a ``stage`` mesh axis inside ``shard_map``; one scan over clocks
advances every stage in parallel and moves activations to the next stage
with ``jax.lax.ppermute``.  Reverse-mode AD through the scan+ppermute yields
the backward pipeline automatically; with PEFT's fwd==bwd stage latency the
resulting schedule matches the paper's symmetric-1F1B timing model, and the
*order* in which micro-batches are fed is exactly the planner's template
(buckets sorted desc, consecutive micro-batches) — the template is data,
not code.

``pipeline_reference`` runs the same clock loop without shard_map (single
device) for semantics tests.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _clock_loop(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves stacked [n_stages, ...] (or per-shard [1, ...])
    microbatches: jax.Array,  # [n_micro, mb, ...]
    n_stages: int,
    shift: Callable[[jax.Array], jax.Array],
    select_stage: Callable[[Any, int], Any],
    my_stage: Optional[jax.Array] = None,
):
    n_micro = microbatches.shape[0]
    clocks = n_micro + n_stages - 1
    mb_shape = microbatches.shape[1:]
    state = jnp.zeros((1,) + mb_shape, microbatches.dtype) if my_stage is not None else jnp.zeros(
        (n_stages,) + mb_shape, microbatches.dtype
    )
    outputs = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)

    def clock(carry, t):
        state, outputs = carry
        # inject the next microbatch at stage 0
        inject = jnp.where(t < n_micro, 1, 0)
        mb = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False
        )
        if my_stage is not None:  # shard_map path: local slice is [1, ...]
            is_first = (my_stage == 0)
            cur = jnp.where(is_first & (inject == 1), mb[None], state)
            y = stage_fn(select_stage(stage_params, 0), cur[0])[None]
        else:  # reference path: vmap over all stages
            cur = state.at[0].set(jnp.where(inject == 1, mb, state[0]))
            y = jax.vmap(stage_fn)(stage_params, cur)
        out_mb = t - (n_stages - 1)
        if my_stage is not None:
            last_y = y[0]
            take = (my_stage == n_stages - 1) & (out_mb >= 0)
            outputs = jax.lax.cond(
                take,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, last_y, jnp.maximum(out_mb, 0), axis=0),
                lambda o: o,
                outputs,
            )
        else:
            outputs = jax.lax.cond(
                out_mb >= 0,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y[-1], jnp.maximum(out_mb, 0), axis=0),
                lambda o: o,
                outputs,
            )
        state = shift(y)
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(clock, (state, outputs), jnp.arange(clocks))
    return outputs


def pipeline_reference(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # [n_stages, ...]
    microbatches: jax.Array,
    n_stages: int,
) -> jax.Array:
    """Single-device clock-accurate reference (for tests)."""

    def shift(y):
        return jnp.concatenate([jnp.zeros_like(y[:1]), y[:-1]], axis=0)

    return _clock_loop(stage_fn, stage_params, microbatches, n_stages, shift,
                       select_stage=lambda p, i: p)


def pipeline_shard_map(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves stacked [n_stages, ...]
    microbatches: jax.Array,  # [n_micro, mb, ...]
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jax.Array:
    """shard_map pipeline over ``stage_axis`` with ppermute transfers."""
    n_stages = mesh.shape[stage_axis]

    def body(params_local, micro):
        my_stage = jax.lax.axis_index(stage_axis)

        def shift(y):
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            return jax.lax.ppermute(y, stage_axis, perm)

        outs = _clock_loop(
            stage_fn, params_local, micro, n_stages, shift,
            select_stage=lambda p, i: jax.tree.map(lambda a: a[i], p),
            my_stage=my_stage,
        )
        # only the last stage holds real outputs; broadcast via psum of mask
        is_last = (my_stage == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * is_last, stage_axis)

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)
