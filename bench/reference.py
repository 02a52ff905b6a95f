"""Plain reference of the model's block and its PEFT methods, in float32.

Straightforward ``jax.numpy``: no kernels, no packing, no fused batches, no
grouped adapters, no cache.  Every tenant is computed on its own, one
sequence per row; each sequence attends only to its own earlier tokens
(and to its tenant's prefix rows).  The block stack is the configuration's
block family's (``bench/blocks/<family>.py``: ``stack``), built from the
generic parts here; layers run under ``lax.scan`` with the layer's bfloat16
weights upcast to float32 inside the step, so a wide model fits beside its
own weights.  Matrix products use ``Precision.HIGHEST`` (a float32 product
on a TPU otherwise runs in bfloat16 passes).

``lower`` makes the control: every operand of every matrix product is
rounded to float8 (e4m3) with a per-tensor absmax scale, the step below the
bfloat16 that the configurations state.

The generic parts: RMSNorm, rotary embeddings on the two halves of each
head, and the methods at a matmul site:

* LoRA at a site: ``y += (alpha / r) * (x a) b``;
* bottleneck adapter at a site: ``y += gelu(y down) up``, on the site's
  output (bias included);
* prefix tuning: learned post-rotary key/value rows that every token of the
  tenant's sequences attends to, ahead of its causal context (the family's
  attention takes them).

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

import blocks

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
        self.block = blocks.family(cfg)
        self.n = self.block.dims(cfg)
        self.eps = float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon")))
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
        theta = float(self.cfg["rope_theta"])
        inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
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

    def _stack(self, bb, x, ad, pos, kmask, method, scale):
        """Logits of the configuration's block stack over ``x [N, S, d]``."""
        return self.block.stack(self, bb, x, ad, pos, kmask, method, scale)

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
