"""Fine-grained MoE (token-choice top-k) with expert parallelism.

Distribution strategy (DESIGN.md §5): the residual stream is replicated over
the "model" mesh axis at the MoE boundary; experts are sharded over "model"
(EP).  Each model-rank routes the *same* local token block (identical
routing, deterministic), gathers capacity-C slots for its local experts,
runs the grouped expert FFN as one batched einsum, scatter-adds weighted
outputs, and a single ``psum`` over "model" combines contributions — one
activation-sized all-reduce per MoE layer, no giant dispatch one-hots.

Implemented with ``shard_map`` nested in jit; with no active mesh (tests) the
same core runs locally with all experts.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.distributed.sharding import active_rules
from repro.models.layers import ParamSpec


def moe_spec(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    s = {
        "router": ParamSpec((d, e), ("embed", None), scale=0.006),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "expert_ff")),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "expert_ff")),
        "w_down": ParamSpec((e, f, d), ("experts", "expert_ff", "embed")),
    }
    return s


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(np.ceil(tokens * top_k / n_experts * cf))
    return max(8, (c + 7) // 8 * 8)


def _route(x_flat: jax.Array, router_w: jax.Array, top_k: int):
    """Top-k routing with softmax-renormalized gates (deepseek/qwen style)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, ids = jax.lax.top_k(probs, top_k)  # [t, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), gate_vals, probs


def _aux_losses(probs: jax.Array, ids: jax.Array, n_experts: int) -> Dict[str, jax.Array]:
    """Load-balance (Switch-style) + router z-ish entropy diagnostics."""
    t = probs.shape[0]
    counts = jnp.zeros((n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = probs.mean(axis=0)
    lb = n_experts * jnp.sum(frac_tokens * frac_probs)
    return {"moe_load_balance": lb, "moe_max_frac": frac_tokens.max()}


def _expert_core(
    x_flat: jax.Array,  # [t, d]
    p: Dict[str, jax.Array],  # expert weights already local: [E_loc, d, f] etc.
    ids: jax.Array,  # [t, k] global expert ids
    gates: jax.Array,  # [t, k]
    expert_offset: jax.Array,  # [] int32
    n_local: int,
    capacity: int,
) -> jax.Array:
    """Capacity-gather -> grouped FFN -> weighted scatter-add (local)."""
    t, d = x_flat.shape
    k = ids.shape[1]
    flat_ids = ids.reshape(-1)  # [t*k]
    flat_gate = gates.reshape(-1)
    tok_of = jnp.arange(t * k, dtype=jnp.int32) // k

    def per_expert(e_local):
        e = expert_offset + e_local
        m = flat_ids == e  # [t*k]
        rank = jnp.cumsum(m.astype(jnp.int32)) - 1
        sel = m & (rank < capacity)
        slot = jnp.where(sel, rank, capacity)  # invalid -> dropped slot
        idx = jnp.full((capacity + 1,), t, jnp.int32).at[slot].set(
            jnp.where(sel, tok_of, t), mode="drop"
        )[:capacity]
        gt = jnp.zeros((capacity + 1,), jnp.float32).at[slot].set(
            jnp.where(sel, flat_gate, 0.0), mode="drop"
        )[:capacity]
        return idx, gt

    idx, gt = jax.vmap(per_expert)(jnp.arange(n_local, dtype=jnp.int32))
    # idx/gt: [E_loc, C]; idx == t marks empty slots.
    valid = (idx < t)[..., None].astype(x_flat.dtype)
    xe = jnp.take(x_flat, jnp.minimum(idx, t - 1), axis=0) * valid  # [E_loc, C, d]

    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # [E_loc, C, d]

    ye = ye * gt[..., None].astype(ye.dtype)
    y = jnp.zeros((t + 1, d), ye.dtype).at[idx.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop"
    )[:t]
    return y


def moe_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,  # [B, S, d]
    cfg: ArchConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    mesh, rules = active_rules()
    B, S, d = x.shape
    use_ep = (
        mesh is not None
        and rules is not None
        and rules.lookup("experts") is not None
    )
    if use_ep and rules.lookup("moe_impl") == "a2a":
        return moe_apply_a2a(p, x, cfg)
    if not use_ep:
        x_flat = x.reshape(-1, d)
        ids, gates, probs = _route(x_flat, p["router"], cfg.top_k)
        cap = _capacity(x_flat.shape[0], cfg.top_k, cfg.num_experts, cfg.capacity_factor)
        y = _expert_core(
            x_flat, p, ids, gates, jnp.zeros((), jnp.int32), cfg.num_experts, cap
        )
        aux = _aux_losses(probs, ids, cfg.num_experts)
        return y.reshape(B, S, d).astype(x.dtype), aux

    ep_axis = rules.lookup("experts")
    assert isinstance(ep_axis, str), ep_axis
    ep = mesh.shape[ep_axis]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_local = cfg.num_experts // ep
    b_loc = B // int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else B
    cap = _capacity(b_loc * S, cfg.top_k, cfg.num_experts, cfg.capacity_factor)

    def body(x_loc, router_w, wg, wu, wd):
        t = x_loc.shape[0] * x_loc.shape[1]
        x_flat = x_loc.reshape(t, d)
        ids, gates, probs = _route(x_flat, router_w, cfg.top_k)
        off = jax.lax.axis_index(ep_axis).astype(jnp.int32) * n_local
        pl = {"w_gate": wg, "w_up": wu, "w_down": wd}
        y = _expert_core(x_flat, pl, ids, gates, off, n_local, cap)
        y = jax.lax.psum(y, ep_axis)
        aux = _aux_losses(probs, ids, cfg.num_experts)
        aux = {k: jax.lax.pmean(v, mesh.axis_names) for k, v in aux.items()}
        return y.reshape(x_loc.shape), aux

    bspec = P(dp_axes if dp_axes else None, None, None)
    espec = P(ep_axis, None, None)
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None), espec, espec, espec),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# §Perf beyond-paper path: all-to-all token dispatch (+ FSDP expert weights)
# ---------------------------------------------------------------------------


def moe_apply_a2a(
    p: Dict[str, jax.Array],
    x: jax.Array,  # [B, S, d]
    cfg: ArchConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """A2A-dispatch MoE: tokens stay sequence-sharded over the EP axis; each
    rank routes its own tokens, ships them to expert owners with one
    ``all_to_all``, runs the grouped FFN, and ships results back — no
    residual-stream all-gather, no full-activation psum.  Wire bytes per
    layer drop from ~2*B*S*d (replicated psum) to ~2*(B*S/P)*k*cf*d.

    Optional FSDP for frozen expert weights: when the "moe_fsdp" rule names
    a mesh axis, expert weights arrive sharded on their d_model dim over
    that axis and are all-gathered just-in-time inside the layer (freed
    after) — HBM holds 1/|axis| of the expert bytes at rest.
    """
    mesh, rules = active_rules()
    B, S, d = x.shape
    ep_axis = rules.lookup("experts")
    fsdp_axis = rules.lookup("moe_fsdp")
    int8_wire = rules.lookup("moe_wire") == "int8"
    assert isinstance(ep_axis, str)
    P_sz = mesh.shape[ep_axis]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    n_local = cfg.num_experts // P_sz
    k = cfg.top_k
    t_loc = (B // n_dp) * (S // P_sz)
    c_send = max(8, int(np.ceil(t_loc * k / P_sz * cfg.capacity_factor) + 7) // 8 * 8)
    c_recv_total = P_sz * c_send
    # cf is already applied at dispatch; expert slots only need headroom for
    # imbalance BETWEEN the rank's local experts (sqrt-law fudge, min 1.1x)
    local_imbalance = 1.1 + 0.5 / np.sqrt(max(n_local, 1))
    c_exp = max(8, int(np.ceil(c_recv_total / n_local * local_imbalance) + 7) // 8 * 8)

    def body(x_loc, router_w, wg, wu, wd):
        b_l, s_l, _ = x_loc.shape
        t = b_l * s_l
        xf = x_loc.reshape(t, d)
        ids, gates, probs = _route(xf, router_w, k)  # [t, k]
        flat_ids = ids.reshape(-1)
        flat_gate = gates.reshape(-1)
        tok_of = jnp.arange(t * k, dtype=jnp.int32) // k
        owner = flat_ids // n_local   # destination rank
        local_eid = flat_ids % n_local

        def per_dest(dst):
            m = owner == dst
            r = jnp.cumsum(m.astype(jnp.int32)) - 1
            sel = m & (r < c_send)
            slot = jnp.where(sel, r, c_send)
            def scat(vals, fill, dtype):
                return jnp.full((c_send + 1,), fill, dtype).at[slot].set(
                    jnp.where(sel, vals, fill), mode="drop")[:c_send]
            s_tok = scat(tok_of, t, jnp.int32)        # origin token (t=invalid)
            s_eid = scat(local_eid, 0, jnp.int32)
            s_gate = scat(flat_gate, 0.0, jnp.float32)
            return s_tok, s_eid, s_gate

        s_tok, s_eid, s_gate = jax.vmap(per_dest)(jnp.arange(P_sz, dtype=jnp.int32))
        valid = (s_tok < t)
        send_x = jnp.take(xf, jnp.minimum(s_tok, t - 1), axis=0)
        send_x = send_x * valid[..., None].astype(send_x.dtype)  # [P, C, d]

        # ship tokens to expert owners (optionally int8-quantized wire format:
        # per-token absmax scale; dequantized at the expert — ~2x fewer bytes)
        if int8_wire:
            absmax = jnp.max(jnp.abs(send_x.astype(jnp.float32)), axis=-1,
                             keepdims=True) / 127.0
            qx = jnp.clip(jnp.round(send_x.astype(jnp.float32) /
                                    jnp.maximum(absmax, 1e-12)), -127, 127
                          ).astype(jnp.int8)
            rq = jax.lax.all_to_all(qx, ep_axis, split_axis=0, concat_axis=0, tiled=True)
            rs = jax.lax.all_to_all(absmax.astype(jnp.float32), ep_axis,
                                    split_axis=0, concat_axis=0, tiled=True)
            rx = (rq.astype(jnp.float32) * rs).astype(send_x.dtype)
        else:
            rx = jax.lax.all_to_all(send_x, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        r_eid = jax.lax.all_to_all(s_eid, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        r_gate = jax.lax.all_to_all(s_gate, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        r_valid = jax.lax.all_to_all(
            valid.astype(jnp.int32), ep_axis, split_axis=0, concat_axis=0, tiled=True)

        rxf = rx.reshape(c_recv_total, d)
        flat_eid = r_eid.reshape(-1)
        flat_rgate = r_gate.reshape(-1) * r_valid.reshape(-1).astype(jnp.float32)

        if fsdp_axis:
            wg_f = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd_f = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        else:
            wg_f, wu_f, wd_f = wg, wu, wd

        # per-local-expert capacity gather + grouped FFN
        def per_expert(e):
            m = (flat_eid == e) & (flat_rgate > 0)
            r = jnp.cumsum(m.astype(jnp.int32)) - 1
            sel = m & (r < c_exp)
            slot = jnp.where(sel, r, c_exp)
            idx = jnp.full((c_exp + 1,), c_recv_total, jnp.int32).at[slot].set(
                jnp.where(sel, jnp.arange(c_recv_total, dtype=jnp.int32), c_recv_total),
                mode="drop")[:c_exp]
            return idx

        idx = jax.vmap(per_expert)(jnp.arange(n_local, dtype=jnp.int32))  # [E_loc, C2]
        e_valid = (idx < c_recv_total)[..., None].astype(rxf.dtype)
        xe = jnp.take(rxf, jnp.minimum(idx, c_recv_total - 1), axis=0) * e_valid

        g = jnp.einsum("ecd,edf->ecf", xe, wg_f)
        u = jnp.einsum("ecd,edf->ecf", xe, wu_f)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
        ye = jnp.einsum("ecf,efd->ecd", h, wd_f)  # [E_loc, C2, d]

        # scatter back to recv slots, apply gates, return trip
        back = jnp.zeros((c_recv_total + 1, d), ye.dtype).at[idx.reshape(-1)].add(
            ye.reshape(-1, d), mode="drop")[:c_recv_total]
        back = back * flat_rgate[:, None].astype(back.dtype)
        back = back.reshape(P_sz, c_send, d)
        ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0, tiled=True)

        # combine at origin
        y = jnp.zeros((t + 1, d), ret.dtype).at[s_tok.reshape(-1)].add(
            ret.reshape(-1, d), mode="drop")[:t]
        aux = _aux_losses(probs, ids, cfg.num_experts)
        aux = {kk: jax.lax.pmean(v, mesh.axis_names) for kk, v in aux.items()}
        return y.reshape(b_l, s_l, d), aux

    bspec = P(dp_axes if dp_axes else None, ep_axis, None)
    if fsdp_axis:
        espec_in = P(ep_axis, fsdp_axis, None)
        espec_out = P(ep_axis, None, fsdp_axis)
    else:
        espec_in = espec_out = P(ep_axis, None, None)
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None), espec_in, espec_in, espec_out),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.astype(x.dtype), aux
