"""Plain reference of the dense decoder and its PEFT methods, in float32.

Straightforward ``jax.numpy``: no kernels, no packing, no fused batches, no
grouped adapters, no cache.  Every tenant is computed on its own, one
sequence per row; each sequence attends only to its own earlier tokens
(and to its tenant's prefix rows).  Layers run under ``lax.scan`` with the
layer's bfloat16 weights upcast to float32 inside the step, so a wide
model fits beside its own weights.  Matrix products use
``Precision.HIGHEST`` (a float32 product on a TPU otherwise runs in
bfloat16 passes).

``lower`` makes the control: every operand of every matrix product is
rounded to float8 (e4m3) with a per-tensor absmax scale, the step below the
bfloat16 that the configurations state.

The block follows the configuration file: RMSNorm, rotary embeddings on the
two halves of each head, grouped-query causal attention, SwiGLU or tanh-GELU
MLP, optional attention biases, tied or untied LM head.  The methods:

* LoRA at a site: ``y += (alpha / r) * (x a) b``;
* bottleneck adapter at a site: ``y += gelu(y down) up``, on the site's
  output (bias included);
* prefix tuning: learned post-rotary key/value rows that every token of the
  tenant's sequences attends to, ahead of its causal context.

The optimizer is AdamW with the hyperparameters the caller passes (the
ones the program under test states), bias-corrected at each tenant's own
update count.

Nothing here imports the program under test.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from work import dims

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return jnp.clip(a / s, -F8_MAX, F8_MAX).astype(F8).astype(jnp.float32) * s


@jax.custom_vjp
def _round_f8(a):
    """``a`` rounded to float8 e4m3 under a per-tensor absmax scale; the
    gradient that flows back through it is rounded the same way, so the
    backward pass's products take float8 operands too."""
    return _q8(a.astype(jnp.float32))


def _round_f8_fwd(a):
    return _round_f8(a), None


def _round_f8_bwd(_, g):
    return (_q8(g),)


_round_f8.defvjp(_round_f8_fwd, _round_f8_bwd)


class Reference:
    """One configuration's reference forward, loss and gradient."""

    def __init__(self, cfg: Dict, lower: bool = False):
        self.cfg = cfg
        self.n = dims(cfg)
        self.eps = float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon")))
        self.theta = float(cfg["rope_theta"])
        self.swiglu = cfg["block"]["mlp"] == "swiglu"
        self.lower = lower

    def _mm(self, spec: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.lower:
            a, b = _round_f8(a), _round_f8(b)
        return jnp.einsum(spec, a, b, precision=HI)

    def _rms(self, x, w):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * w.astype(jnp.float32)

    def _rope(self, x, pos):
        """x [N, S, H, dh] float32, pos [N, S]."""
        dh = x.shape[-1]
        inv = 1.0 / (self.theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
        ang = pos[..., None].astype(jnp.float32) * inv
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    # ------------------------------------------------------------------
    # one tenant's site rule

    def _site(self, ad, name, x, y, method, scale):
        """Output of a matmul site (``y`` its base output, ``x`` its input)
        with the tenant's method applied; ``ad`` holds this layer's
        leaves."""
        if ad is None or name not in ad:
            return y
        p = ad[name]
        N, S = x.shape[:2]
        xf = x.reshape(N, S, -1)
        yf = y.reshape(N, S, -1)
        if method == "lora":
            add = scale * self._mm("nsr,ro->nso",
                                   self._mm("nsi,ir->nsr", xf, p["a"]), p["b"])
        else:
            h = jax.nn.gelu(self._mm("nsi,ir->nsr", yf, p["down"]), approximate=True)
            add = self._mm("nsr,ro->nso", h, p["up"])
        return (yf + add).reshape(y.shape)

    def _layer(self, x, lw, ad, pos, kmask, method, scale, prefix):
        """One block over ``x [N, S, d]``.  ``kmask [N, S]`` marks the real
        tokens (keys); attention is causal within each row.  ``prefix`` is
        this layer's (pk, pv) [P, kv] or None."""
        n = self.n
        N, S, _ = x.shape
        h = self._rms(x, lw["ln1"])
        q = self._mm("nsd,dhk->nshk", h, lw["wq"])
        k = self._mm("nsd,dhk->nshk", h, lw["wk"])
        v = self._mm("nsd,dhk->nshk", h, lw["wv"])
        if "bq" in lw:
            q = q + lw["bq"].astype(jnp.float32)
            k = k + lw["bk"].astype(jnp.float32)
            v = v + lw["bv"].astype(jnp.float32)
        q = self._site(ad, "attn_q", h, q, method, scale)
        k = self._site(ad, "attn_k", h, k, method, scale)
        v = self._site(ad, "attn_v", h, v, method, scale)
        q, k = self._rope(q, pos), self._rope(k, pos)
        G = n["H"] // n["Hkv"]
        qg = q.reshape(N, S, n["Hkv"], G, n["dh"])
        s = self._mm("nskgd,ntkd->nkgst", qg, k) / np.sqrt(n["dh"])
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        allow = causal[None] & kmask[:, None, :]
        s = jnp.where(allow[:, None, None], s, -jnp.inf)
        if prefix is not None:
            P = prefix[0].shape[0]
            pk = prefix[0].reshape(P, n["Hkv"], n["dh"])
            pv = prefix[1].reshape(P, n["Hkv"], n["dh"])
            sp = self._mm("nskgd,pkd->nkgsp", qg, pk) / np.sqrt(n["dh"])
            w = jax.nn.softmax(jnp.concatenate([sp, s], axis=-1), axis=-1)
            o = (self._mm("nkgst,ntkd->nskgd", w[..., P:], v)
                 + self._mm("nkgsp,pkd->nskgd", w[..., :P], pv))
        else:
            w = jax.nn.softmax(s, axis=-1)
            o = self._mm("nkgst,ntkd->nskgd", w, v)
        o = o.reshape(N, S, n["H"] * n["dh"])
        y = self._mm("nsq,qd->nsd", o, lw["wo"].reshape(n["H"] * n["dh"], -1))
        if "bo" in lw:
            y = y + lw["bo"].astype(jnp.float32)
        y = self._site(ad, "attn_o", o, y, method, scale)
        x = x + y
        h = self._rms(x, lw["ln2"])
        if self.swiglu:
            g = self._site(ad, "mlp_gate", h, self._mm("nsd,df->nsf", h, lw["w_gate"]),
                           method, scale)
            u = self._site(ad, "mlp_up", h, self._mm("nsd,df->nsf", h, lw["w_up"]),
                           method, scale)
            m = jax.nn.silu(g) * u
            y = self._site(ad, "mlp_down", m, self._mm("nsf,fd->nsd", m, lw["w_down"]),
                           method, scale)
        else:
            f = self._site(ad, "mlp_fc1", h, self._mm("nsd,df->nsf", h, lw["w_fc1"]),
                           method, scale)
            m = jax.nn.gelu(f, approximate=True)
            y = self._site(ad, "mlp_fc2", m, self._mm("nsf,fd->nsd", m, lw["w_fc2"]),
                           method, scale)
        return x + y

    def _stack(self, bb, x, ad, pos, kmask, method, scale):
        site_ad = {k: v for k, v in ad.items() if k != "attn_prefix"}
        prefix = None
        if "attn_prefix" in ad:
            prefix = (ad["attn_prefix"]["pk"], ad["attn_prefix"]["pv"])

        @jax.checkpoint
        def body(x, xs):
            lw, a, pre = xs
            return self._layer(x, lw, a, pos, kmask, method, scale, pre), None

        x, _ = jax.lax.scan(body, x, (bb["layers"], site_ad, prefix))
        x = self._rms(x, bb["final_norm"])
        if "unembed" in bb:
            return self._mm("nsd,dv->nsv", x, bb["unembed"])
        return self._mm("nsd,vd->nsv", x, bb["tok"])

    # ------------------------------------------------------------------
    # training: one tenant's loss and gradient

    def tenant_loss(self, ad, bb, batch, method, scale):
        """Masked mean next-token loss of one tenant's sequences.
        ``batch``: tokens/labels/mask/valid/pos [N, S], one sequence a row."""
        x = jnp.take(bb["tok"], batch["tokens"], axis=0).astype(jnp.float32)
        logits = self._stack(bb, x, ad, batch["pos"], batch["valid"], method,
                             scale)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
        m = batch["mask"]
        return jnp.sum((lse - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)


# ---------------------------------------------------------------------------
# AdamW, no weight decay


def adamw_step(params, grads, m, v, count, lr: float, b1: float, b2: float,
               eps: float):
    """One AdamW update with bias correction at the tenant's own count
    (``count``, the update's number from 1)."""
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
        params, m, v)
    return params, m, v


def sequences_batch(seqs: List[Dict], pad_to: Optional[int] = None) -> Dict:
    """Rows of one tenant: each ``{"tokens", "last_label_zero"}``.  The last
    real token's label follows the program's batch layout: the pad id 0
    when the sequence leaves part of its chunk footprint empty, otherwise it
    carries no loss."""
    S = pad_to or max(len(s["tokens"]) for s in seqs)
    N = len(seqs)
    tok = np.zeros((N, S), np.int32)
    lab = np.zeros((N, S), np.int32)
    msk = np.zeros((N, S), np.float32)
    val = np.zeros((N, S), bool)
    for i, s in enumerate(seqs):
        t = np.asarray(s["tokens"], np.int32)
        n = len(t)
        tok[i, :n] = t
        lab[i, :n - 1] = t[1:]
        msk[i, :n - 1] = 1.0
        if s["last_label_zero"]:
            msk[i, n - 1] = 1.0
        val[i, :n] = True
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (N, S)).copy()
    return {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab),
            "mask": jnp.asarray(msk), "valid": jnp.asarray(val),
            "pos": jnp.asarray(pos)}
