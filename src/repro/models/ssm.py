"""SSM blocks: chunked gated linear attention (SSD) core, Mamba2, m/sLSTM.

One chunked-scan primitive serves both Mamba2 (SSD with per-head scalar
decay ``exp(dt*A)``) and mLSTM (sigmoid-gated matrix memory; the xLSTM
normalizer state rides along as an extra ``v`` column).  sLSTM is a strict
time recurrence (scalar memory + per-head recurrent matrices) via
``lax.scan`` over time — inherently sequential, as in the paper.

State recurrence per head (all in f32):
    H_t = exp(la_t) * H_{t-1} + exp(li_t) * k_t (x) v_t
    y_t = q_t . H_t
Chunked evaluation: intra-chunk block attention with decay mask +
inter-chunk state carry — O(S*(Q*dk + dk*dv)) instead of O(S^2).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import CONV_W, ArchConfig
from repro.distributed.sharding import shard
from repro.models.layers import ParamSpec, rms_norm
from repro.peft.hooks import apply_base_op

# ---------------------------------------------------------------------------
# Chunked GLA core
# ---------------------------------------------------------------------------


def chunked_gla(
    q: jax.Array,  # [B, S, H, dk]
    k: jax.Array,  # [B, S, H, dk]
    v: jax.Array,  # [B, S, H, dv]
    log_decay: jax.Array,  # [B, S, H]  (<= 0)
    log_input: jax.Array,  # [B, S, H]
    chunk: int,
    h0: Optional[jax.Array] = None,  # [B, H, dk, dv]
    reset: Optional[jax.Array] = None,  # [B, S] 1.0 where a new segment starts
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,H,dv], final_state [B,H,dk,dv]).

    This is the ``xla`` tier of ``kernels.ops.mamba_scan`` (the model cells
    route through that dispatcher; ``set_impl("pallas")`` swaps in the
    Pallas kernel with its custom_vjp backward).  ``reset`` implements the
    §3.5 chunk-alignment *state-carry dependency* for packed sequences: a
    reset position zeroes the decay from everything before it (the SSM
    analogue of the KV-reuse boundary).
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    n = S // Q

    if reset is not None:
        # State erasure uses EXACT segment masks, not a -1e9 log-decay
        # sentinel: a sentinel summed into the f32 in-chunk cumsum absorbs
        # every later decay in that chunk (ulp at 1e9 is ~64), so all
        # post-reset pairs would decay by exp(0) = 1.  The reset position's
        # decay is excluded from the cumsum instead (its gradient is zeroed
        # by this where) and cross-segment interaction is cut by comparing
        # within-chunk reset counts below.
        log_decay = jnp.where(reset[:, :, None] > 0, 0.0, log_decay)

    def to_chunks(x):
        return jnp.moveaxis(x.reshape((B, n, Q) + x.shape[2:]), 1, 0)

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lac, lic = to_chunks(log_decay.astype(jnp.float32)), to_chunks(log_input.astype(jnp.float32))
    rc = to_chunks((reset > 0).astype(jnp.int32)) if reset is not None else None

    if h0 is None:
        h0 = jnp.zeros((B, H, dk, dv), jnp.float32)

    causal = np.tril(np.ones((Q, Q), np.float32))

    def step(hprev, xs, ri=None):
        qi, ki, vi, la, li = xs  # [B, Q, H, *]
        cum = jnp.cumsum(la, axis=1)  # [B, Q, H] inclusive; non-increasing
        gain = jnp.exp(li)  # [B, Q, H] input gate magnitude (may exceed 1)
        # intra-chunk: scores_ij = (q_i . k_j) * exp(cum_i - cum_j) * gain_j, j<=i
        dec = cum[:, :, None, :] - cum[:, None, :, :]  # <= 0 for j <= i
        cmask = causal[None, :, :, None]
        dec = jnp.exp(dec * cmask) * cmask * gain[:, None, :, :]
        qd = qi.astype(jnp.float32) * jnp.exp(cum)[..., None]
        total = cum[:, -1:, :]  # [B,1,H]
        w = jnp.exp(total - cum) * gain  # total - cum <= 0
        hscale = jnp.exp(total[:, 0, :])  # [B,H]
        if ri is not None:
            # positions interact iff their within-chunk reset counts match;
            # H_prev reaches rows before the first reset; only the final
            # sub-segment feeds the carried state
            seg = jnp.cumsum(ri, axis=1)  # [B, Q]
            dec = dec * (seg[:, :, None] == seg[:, None, :]
                         ).astype(jnp.float32)[..., None]
            qd = qd * (seg == 0).astype(jnp.float32)[:, :, None, None]
            w = w * (seg == seg[:, -1:]).astype(jnp.float32)[..., None]
            hscale = hscale * (seg[:, -1] == 0).astype(jnp.float32)[:, None]
        s = jnp.einsum("bihd,bjhd->bijh", qi, ki, preferred_element_type=jnp.float32)
        y_intra = jnp.einsum("bijh,bjhv->bihv", s * dec, vi.astype(jnp.float32))
        # inter-chunk: y_i += exp(cum_i) * q_i . H_prev
        y_inter = jnp.einsum("bihd,bhdv->bihv", qd, hprev)
        # state update: H_new = exp(cum_Q) H_prev + sum_j exp(cum_Q - cum_j) gain_j k_j v_j
        kd = ki.astype(jnp.float32) * w[..., None]
        h_new = (
            hscale[:, :, None, None] * hprev
            + jnp.einsum("bjhd,bjhv->bhdv", kd, vi.astype(jnp.float32))
        )
        return h_new, (y_intra + y_inter).astype(q.dtype)

    from repro.models.flags import cost_unroll

    if rc is None:
        scan_step, xs = step, (qc, kc, vc, lac, lic)
    else:
        def scan_step(hprev, xs_r):
            return step(hprev, xs_r[:-1], ri=xs_r[-1])
        xs = (qc, kc, vc, lac, lic, rc)
    # Cost-measurement unrolling is capped: beyond 32 chunks the HLO blowup
    # makes CPU compiles intractable; the roofline builder adds the analytic
    # (n_chunks - 1) x per-chunk GLA correction for those cells instead.
    h_final, yc = jax.lax.scan(scan_step, h0, xs,
                               unroll=cost_unroll() and n <= 32)
    y = jnp.moveaxis(yc, 0, 1).reshape(B, S, H, dv)
    return y, h_final


def gla_decode_step(
    q: jax.Array,  # [B, 1, H, dk]
    k: jax.Array,
    v: jax.Array,  # [B, 1, H, dv]
    log_decay: jax.Array,  # [B, 1, H]
    log_input: jax.Array,
    h: jax.Array,  # [B, H, dk, dv]
) -> Tuple[jax.Array, jax.Array]:
    a = jnp.exp(log_decay.astype(jnp.float32))[:, 0, :, None, None]
    b = jnp.exp(log_input.astype(jnp.float32))[:, 0, :, None, None]
    kv = jnp.einsum("bhd,bhv->bhdv", k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32))
    h_new = a * h + b * kv
    y = jnp.einsum("bhd,bhdv->bhv", q[:, 0].astype(jnp.float32), h_new)
    return y[:, None].astype(q.dtype), h_new


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def mamba2_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    return d_in, n_heads, cfg.ssm_state


def mamba2_spec(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    d_in, nh, st = mamba2_dims(cfg)
    # in-proj: [z (d_in), x (d_in), B (st), C (st), dt (nh)]
    s = {
        "w_in": ParamSpec((d, 2 * d_in + 2 * st + nh), ("embed", "ssm_inner")),
        "conv": ParamSpec((CONV_W, d_in + 2 * st), (None, "ssm_inner"), scale=0.1),
        "dt_bias": ParamSpec((nh,), (None,), init="zeros"),
        "a_log": ParamSpec((nh,), (None,), init="ones", scale=1.0),
        "d_skip": ParamSpec((nh,), (None,), init="ones"),
        "norm": ParamSpec((d_in,), ("ssm_inner",), init="ones"),
        "w_out": ParamSpec((d_in, d), ("ssm_inner", "embed")),
    }
    if cfg.ssm_conv_bias:
        s["conv_b"] = ParamSpec((d_in + 2 * st,), ("ssm_inner",), init="zeros")
    return s


def _causal_depthwise_conv(x: jax.Array, w: jax.Array,
                           b: Optional[jax.Array] = None) -> jax.Array:
    """x: [B, S, C]; w: [W, C]; b: [C] or None — causal depthwise conv."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x) if b is None else jnp.broadcast_to(b, x.shape).astype(x.dtype)
    for i in range(W):  # W is tiny (4): unrolled taps
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    return out


def mamba2_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,  # [B, S, d]
    cfg: ArchConfig,
    state: Optional[Dict[str, jax.Array]] = None,  # decode: {"h","conv"}
    reset: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, d = x.shape
    d_in, nh, st = mamba2_dims(cfg)
    hd = cfg.ssm_head_dim

    proj = apply_base_op("ssm_in", x, p["w_in"], "bsd,de->bse")
    z, xin, bmat, cmat, dt_raw = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + st, 2 * d_in + 2 * st], axis=-1
    )
    conv_in = jnp.concatenate([xin, bmat, cmat], axis=-1)
    if state is None:
        conv_out = _causal_depthwise_conv(conv_in, p["conv"], p.get("conv_b"))
    else:
        # decode: roll the conv window buffer [B, CONV_W-1, C]
        buf = jnp.concatenate([state["conv"], conv_in], axis=1)
        conv_out = (buf[:, -CONV_W:, :] * p["conv"][None]).sum(axis=1, keepdims=True)
        if "conv_b" in p:
            conv_out = conv_out + p["conv_b"]
        state = dict(state, conv=buf[:, 1:, :])
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    xin, bmat, cmat = jnp.split(conv_out, [d_in, d_in + st], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))  # [nh] < 0
    log_decay = dt * a  # [B, S, nh]
    log_input = jnp.log(jnp.maximum(dt, 1e-9))

    v = xin.reshape(B, S, nh, hd)
    k = jnp.broadcast_to(bmat[:, :, None, :], (B, S, nh, st))
    q = jnp.broadcast_to(cmat[:, :, None, :], (B, S, nh, st))
    q = shard(q, "batch", None, "ssm_heads", None)
    v = shard(v, "batch", None, "ssm_heads", None)

    if state is None:
        # Routed through kernels.ops so ``set_impl("pallas")`` runs the
        # chunked-scan Pallas kernel — forward AND backward via its
        # custom_vjp — in the training hot loop; the default "xla" impl
        # dispatches right back to chunked_gla below.
        from repro.kernels import ops as kops

        y, _ = kops.mamba_scan(q, k, v, log_decay, log_input,
                               chunk=cfg.ssm_chunk, reset=reset)
        new_state = None
    else:
        y, h_new = gla_decode_step(q, k, v, log_decay, log_input, state["h"])
        new_state = dict(state, h=h_new)

    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * v.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    out = apply_base_op("ssm_out", y, p["w_out"], "bse,ed->bsd")
    return out, new_state


def mamba2_init_state(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16):
    d_in, nh, st = mamba2_dims(cfg)
    return {
        "h": jnp.zeros((batch, nh, st, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, CONV_W - 1, d_in + 2 * st), dtype),
    }


# ---------------------------------------------------------------------------
# mLSTM block (xLSTM matrix memory; normalizer via v-augmentation)
# ---------------------------------------------------------------------------


def mlstm_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = cfg.num_heads
    return d_in, nh, d_in // nh


def mlstm_spec(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    d_in, nh, hd = mlstm_dims(cfg)
    return {
        "w_up": ParamSpec((d, 2 * d_in), ("embed", "ssm_inner")),
        "w_q": ParamSpec((d_in, d_in), ("ssm_inner", None)),
        "w_k": ParamSpec((d_in, d_in), ("ssm_inner", None)),
        "w_v": ParamSpec((d_in, d_in), ("ssm_inner", None)),
        "w_gates": ParamSpec((d_in, 2 * nh), ("ssm_inner", None), scale=0.01),
        "gate_bias": ParamSpec((2 * nh,), (None,), init="zeros"),
        "norm": ParamSpec((d_in,), ("ssm_inner",), init="ones"),
        "w_down": ParamSpec((d_in, d), ("ssm_inner", "embed")),
    }


def mlstm_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ArchConfig,
    state: Optional[Dict[str, jax.Array]] = None,
    reset: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, d = x.shape
    d_in, nh, hd = mlstm_dims(cfg)
    up = apply_base_op("ssm_in", x, p["w_up"], "bsd,de->bse")
    xin, z = jnp.split(up, 2, axis=-1)
    q = apply_base_op("attn_q", xin, p["w_q"], "bse,ef->bsf").reshape(B, S, nh, hd)
    k = apply_base_op("attn_k", xin, p["w_k"], "bse,ef->bsf").reshape(B, S, nh, hd) / np.sqrt(hd)
    v = apply_base_op("attn_v", xin, p["w_v"], "bse,ef->bsf").reshape(B, S, nh, hd)
    gates = jnp.einsum("bse,eg->bsg", xin, p["w_gates"]) + p["gate_bias"]
    f_pre, i_pre = jnp.split(gates.astype(jnp.float32), 2, axis=-1)  # [B,S,nh]
    log_decay = jax.nn.log_sigmoid(f_pre)
    log_input = jax.nn.log_sigmoid(i_pre)

    # Normalizer state rides along as an extra ones-column of v.
    v_aug = jnp.concatenate([v, jnp.ones((B, S, nh, 1), v.dtype)], axis=-1)

    if state is None:
        from repro.kernels import ops as kops

        y_aug, _ = kops.mamba_scan(q, k, v_aug, log_decay, log_input,
                                   chunk=cfg.ssm_chunk, reset=reset)
        new_state = None
    else:
        y_aug, h_new = gla_decode_step(q, k, v_aug, log_decay, log_input, state["h"])
        new_state = dict(state, h=h_new)
    y, nrm = y_aug[..., :hd], y_aug[..., hd:]
    y = y.astype(jnp.float32) / jnp.maximum(jnp.abs(nrm.astype(jnp.float32)), 1.0)
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return apply_base_op("ssm_out", y, p["w_down"], "bse,ed->bsd"), new_state


def mlstm_init_state(cfg: ArchConfig, batch: int):
    d_in, nh, hd = mlstm_dims(cfg)
    return {"h": jnp.zeros((batch, nh, hd, hd + 1), jnp.float32)}


# ---------------------------------------------------------------------------
# sLSTM block (scalar memory, strict recurrence)
# ---------------------------------------------------------------------------


def slstm_spec(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    nh = cfg.num_heads
    hd = d // nh
    return {
        "w_in": ParamSpec((d, 4 * d), ("embed", None)),
        "r": ParamSpec((nh, hd, 4 * hd), (None, None, None), scale=0.01),
        "norm": ParamSpec((d,), ("embed",), init="ones"),
        "w_out": ParamSpec((d, d), ("embed", None)),
    }


def slstm_apply(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ArchConfig,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    pre = apply_base_op("ssm_in", x, p["w_in"], "bsd,de->bse")
    pre = pre.reshape(B, S, nh, 4 * hd).astype(jnp.float32)

    if state is None:
        c0 = jnp.zeros((B, nh, hd), jnp.float32)
        n0 = jnp.zeros((B, nh, hd), jnp.float32)
        m0 = jnp.zeros((B, nh, hd), jnp.float32)
        h0 = jnp.zeros((B, nh, hd), jnp.float32)
    else:
        c0, n0, m0, h0 = state["c"], state["n"], state["m"], state["h"]

    r = p["r"].astype(jnp.float32)

    def step(carry, pre_t):  # pre_t: [B, nh, 4*hd]
        c, n, m, h = carry
        rec = jnp.einsum("bhd,hde->bhe", h, r)
        zt, it, ft, ot = jnp.split(pre_t + rec, 4, axis=-1)
        m_new = jnp.maximum(ft + m, it)
        i_p = jnp.exp(it - m_new)
        f_p = jnp.exp(ft + m - m_new)
        c_new = f_p * c + i_p * jnp.tanh(zt)
        n_new = f_p * n + i_p
        h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    pre_t = jnp.moveaxis(pre, 1, 0)  # [S, B, nh, 4hd]
    (c, n, m, h), ys = jax.lax.scan(step, (c0, n0, m0, h0), pre_t)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, d).astype(x.dtype)
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    out = apply_base_op("ssm_out", y, p["w_out"], "bsd,de->bse")
    new_state = {"c": c, "n": n, "m": m, "h": h} if state is not None else None
    return out, new_state


def slstm_init_state(cfg: ArchConfig, batch: int):
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    z = jnp.zeros((batch, nh, hd), jnp.float32)
    return {"c": z, "n": z, "m": z, "h": z}
