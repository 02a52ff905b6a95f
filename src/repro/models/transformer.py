"""Unified backbone assembly for all assigned architecture families.

One ``Model`` facade supports: dense/VLM llama-style GQA, fine-grained MoE,
hybrids that repeat a period of Mamba-2 and attention layers (Zamba2: MLP-less
Mamba-2 layers and one weight-shared attention+MLP block; Granite-4.0-H: every
layer a mixer plus its own MLP), xLSTM (mLSTM/sLSTM super-blocks), and
Whisper-style encoder-decoder (stub conv frontend — precomputed frame
embeddings in).

Layers are stored stacked ``[L, ...]`` and executed with ``lax.scan`` (+
optional ``jax.checkpoint`` remat) for compact HLO and O(1) per-layer
activation memory; ``cfg.scan_layers=False`` unrolls (smoke tests).

Adapters (multi-task PEFT) enter as an explicit pytree argument mirroring
the stacked layout; inside the scan each layer's slice is installed into the
BaseOp hook scope, so ``jax.grad`` w.r.t. the adapter argument yields
adapter-only gradients — the backbone is frozen by construction.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import ssm
from repro.models.layers import (
    ParamSpec,
    abstract,
    embed_apply,
    embed_spec,
    layer_norm,
    materialize,
    mlp_apply,
    mlp_spec,
    pad_vocab,
    rms_norm,
    sinusoidal_positions,
    spec_logical_axes,
    unembed_apply,
)
from repro.models.moe import moe_apply, moe_spec
from repro.peft.hooks import adapter_scope

CtxFactory = Callable[[Any], Any]  # layer-adapter slice -> AdapterContext


def _norm_spec(d: int, audio: bool) -> Dict[str, ParamSpec]:
    if audio:
        return {
            "w": ParamSpec((d,), ("embed",), init="ones"),
            "b": ParamSpec((d,), ("embed",), init="zeros"),
        }
    return {"w": ParamSpec((d,), ("embed",), init="ones")}


def _apply_norm(p: Dict[str, jax.Array], x: jax.Array, eps: float) -> jax.Array:
    if "b" in p:
        return layer_norm(x, p["w"], p["b"], eps)
    return rms_norm(x, p["w"], eps)


def _stack_specs(spec: Any, n: int) -> Any:
    """Prefix every ParamSpec in the tree with a stacked layer dim."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale, s.dtype),
        spec,
        is_leaf=lambda s: isinstance(s, ParamSpec),
    )


def _slice_layer(tree: Any, i) -> Any:
    return jax.tree.map(lambda a: a[i], tree)


def _scan_or_loop(body, carry, xs, length: int, use_scan: bool):
    """lax.scan when compact HLO is wanted; an unrolled loop for smoke
    tests."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i] if a is not None else None, xs,
                          is_leaf=lambda v: v is None)
        carry, y = body(carry, xi)
        ys.append(y)
    stacked = None
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return carry, stacked


def _times(a: jax.Array, m: float) -> jax.Array:
    """``a * m`` computed in float32: the multiplier rounded to a bfloat16
    ``a``'s type would scale by another number (0.22 becomes 0.2197)."""
    return a if m == 1.0 else (a.astype(jnp.float32) * m).astype(a.dtype)


class Model:
    """Backbone facade.  ``attn_mode`` in {"pairs", "kvscan"} (DESIGN.md §5)."""

    def __init__(self, cfg: ArchConfig, attn_mode: str = "pairs"):
        self.cfg = cfg
        self.attn_mode = attn_mode
        self.vocab_padded = pad_vocab(cfg.vocab_size)
        self._spec = self._build_spec()

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------

    def _layer_spec(self) -> Dict[str, Any]:
        cfg = self.cfg
        audio = cfg.family == "audio"
        s: Dict[str, Any] = {
            "ln1": _norm_spec(cfg.d_model, audio),
            "attn": attn.attention_spec(cfg),
            "ln2": _norm_spec(cfg.d_model, audio),
        }
        if cfg.family == "moe":
            s["moe"] = moe_spec(cfg)
            if cfg.num_shared_experts:
                s["shared_mlp"] = mlp_spec(
                    cfg.d_model, cfg.num_shared_experts * cfg.expert_d_ff, cfg.gated_mlp
                )
        else:
            s["mlp"] = mlp_spec(cfg.d_model, cfg.d_ff, cfg.gated_mlp, bias=audio and cfg.attention_bias)
        return s

    def _build_spec(self) -> Dict[str, Any]:
        cfg = self.cfg
        spec: Dict[str, Any] = {
            "embed": embed_spec(self.vocab_padded, cfg.d_model, cfg.tie_embeddings),
            "final_norm": _norm_spec(cfg.d_model, cfg.family == "audio"),
        }
        if cfg.family in ("dense", "vlm", "moe"):
            spec["layers"] = _stack_specs(self._layer_spec(), cfg.num_layers)
        elif cfg.family == "hybrid":
            # per kind, [period, index within the period, ...]
            n_per, pat = cfg.n_periods, cfg.layer_pattern
            mamba_layer = {"ln": _norm_spec(cfg.d_model, False), "mamba": ssm.mamba2_spec(cfg)}
            if cfg.mamba_mlp:
                mamba_layer["ln2"] = _norm_spec(cfg.d_model, False)
                mamba_layer["mlp"] = mlp_spec(cfg.d_model, cfg.d_ff, cfg.gated_mlp)
            spec["blocks"] = {"mamba": _stack_specs(
                _stack_specs(mamba_layer, pat.count("mamba")), n_per)}
            attn_layer = self._layer_spec()
            if cfg.shared_attention:
                spec["shared_attn"] = attn_layer  # one copy, reused every period
            else:
                spec["blocks"]["attn"] = _stack_specs(
                    _stack_specs(attn_layer, pat.count("attention")), n_per)
        elif cfg.family == "ssm":
            n_super = cfg.num_layers // cfg.slstm_period
            per = cfg.slstm_period - 1
            mlstm_layer = {"ln": _norm_spec(cfg.d_model, False), "mlstm": ssm.mlstm_spec(cfg)}
            slstm_layer = {"ln": _norm_spec(cfg.d_model, False), "slstm": ssm.slstm_spec(cfg)}
            spec["blocks"] = {
                "mlstm": _stack_specs(_stack_specs(mlstm_layer, per), n_super),
                "slstm": _stack_specs(slstm_layer, n_super),
            }
        elif cfg.family == "audio":
            enc_layer = {
                "ln1": _norm_spec(cfg.d_model, True),
                "attn": attn.attention_spec(cfg),
                "ln2": _norm_spec(cfg.d_model, True),
                "mlp": mlp_spec(cfg.d_model, cfg.d_ff, cfg.gated_mlp, bias=True),
            }
            dec_layer = dict(self._layer_spec())
            dec_layer["ln_cross"] = _norm_spec(cfg.d_model, True)
            dec_layer["cross"] = attn.attention_spec(cfg)
            spec["encoder"] = _stack_specs(enc_layer, cfg.num_encoder_layers)
            spec["enc_final_norm"] = _norm_spec(cfg.d_model, True)
            spec["layers"] = _stack_specs(dec_layer, cfg.num_layers)
        else:
            raise ValueError(cfg.family)
        return spec

    def spec(self):
        return self._spec

    def init(self, key: jax.Array):
        return materialize(self._spec, key)

    def abstract_params(self):
        return abstract(self._spec)

    def logical_axes(self):
        return spec_logical_axes(self._spec)

    # ------------------------------------------------------------------
    # Blocks
    # ------------------------------------------------------------------

    def _attn_mlp_block(
        self, lp, x, *, causal, positions, mrope_positions, segment_ids, aux_sink,
        collect_kv: bool = False,
    ):
        cfg = self.cfg
        h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
        a = attn.attention_apply(
            lp["attn"], h, cfg,
            mode=self.attn_mode, causal=causal, positions=positions,
            mrope_positions=mrope_positions, segment_ids=segment_ids,
            return_kv=collect_kv,
        )
        if collect_kv:
            a, kv = a
            aux_sink["__kv__"] = kv
        x = shard(x + self._res(a), "batch", "seq", None)
        h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe" and "moe" in lp:
            y, aux = moe_apply(lp["moe"], h, cfg)
            if "shared_mlp" in lp:
                y = y + mlp_apply(lp["shared_mlp"], h, cfg.gated_mlp, prefix="shared_mlp")
            for k, v in aux.items():
                aux_sink[k] = aux_sink.get(k, 0.0) + v
        else:
            y = mlp_apply(lp["mlp"], h, cfg.gated_mlp)
        return shard(x + self._res(y), "batch", "seq", None)

    def _res(self, y):
        """A residual branch under ``residual_multiplier``."""
        return _times(y, self.cfg.residual_multiplier)

    def _embed(self, params, tokens):
        x = embed_apply(params["embed"], tokens)
        return _times(x, self.cfg.embedding_multiplier)

    def _mamba_layer(self, lp, x, *, reset=None, state=None):
        """One Mamba-2 layer: the mixer (spanned ``mixer.mamba``, in- through
        out-projection), then its own MLP where the configuration has one.
        ``state`` is the decode state ({"h", "conv"}) or None."""
        cfg = self.cfg
        h = _apply_norm(lp["ln"], x, cfg.norm_eps)
        with jax.named_scope("mixer.mamba"):
            y, state = ssm.mamba2_apply(lp["mamba"], h, cfg, state=state, reset=reset)
        x = shard(x + self._res(y), "batch", "seq", None)
        if "mlp" in lp:
            h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
            y = mlp_apply(lp["mlp"], h, cfg.gated_mlp)
            x = shard(x + self._res(y), "batch", "seq", None)
        return x, state

    def _period(self):
        """The layers of one hybrid period, in order: (mixer, parameter group,
        index within the group's period stack; None for the shared block)."""
        cfg = self.cfg
        seen: Dict[str, int] = {}
        out = []
        for mixer in cfg.layer_pattern:
            if mixer == "mamba":
                group = "mamba"
            elif mixer == "attention":
                group = "shared_attn" if cfg.shared_attention else "attn"
            else:
                raise ValueError(f"layer kind {mixer!r}")
            i = seen.get(group, 0)
            seen[group] = i + 1
            out.append((mixer, group, None if group == "shared_attn" else i))
        return out

    # ------------------------------------------------------------------
    # Forward (training / prefill)
    # ------------------------------------------------------------------

    def forward(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        adapters: Any = None,
        ctx_factory: Optional[CtxFactory] = None,
        return_logits: bool = False,
        collect_kv: bool = False,
    ) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.family == "audio":
            return self._forward_audio(params, batch, adapters, ctx_factory, return_logits)

        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = batch.get("positions")
        segment_ids = batch.get("segment_ids")
        mrope_positions = batch.get("mrope_positions") if cfg.mrope else None
        reset = batch.get("reset")  # SSM segment-boundary resets

        x = self._embed(params, tokens)
        x = shard(x, "batch", "seq", None)
        aux: Dict[str, jax.Array] = {}

        if cfg.family in ("dense", "vlm", "moe"):
            x, aux = self._run_stack(
                params["layers"], x, adapters, ctx_factory,
                collect_kv=collect_kv,
                positions=positions, mrope_positions=mrope_positions,
                segment_ids=segment_ids,
            )
        elif cfg.family == "hybrid":
            x, aux = self._run_hybrid(
                params, x, adapters, ctx_factory,
                positions=positions, segment_ids=segment_ids, reset=reset,
            )
        elif cfg.family == "ssm":
            x, aux = self._run_xlstm(params, x, adapters, ctx_factory, reset=reset)

        kv = aux.pop("__kv__", None)
        x = _apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        out: Dict[str, Any] = {"aux": aux}
        if collect_kv:
            out["kv"] = kv
        if return_logits:
            out["logits"] = logits
        if "labels" in batch:
            out["per_token_loss"] = self._per_token_loss(logits, batch)
        return out

    def _logits(self, params, x):
        logits = unembed_apply(params["embed"], x)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(self.cfg.logits_scaling, logits.dtype)
        if self.vocab_padded != self.cfg.vocab_size:
            pad_mask = jnp.arange(self.vocab_padded) >= self.cfg.vocab_size
            logits = jnp.where(pad_mask, -1e9, logits.astype(jnp.float32)).astype(logits.dtype)
        return shard(logits, "batch", "seq", "vocab")

    def _per_token_loss(self, logits, batch):
        labels = batch["labels"]
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32)
        return (lse - ll) * mask.astype(jnp.float32)

    # ---- dense / vlm / moe stack ----

    def _run_stack(self, layers, x, adapters, ctx_factory, collect_kv=False, **kw):
        cfg = self.cfg
        aux: Dict[str, jax.Array] = {}

        def body(x, lp, ad):
            sink: Dict[str, jax.Array] = {}
            with adapter_scope(ctx_factory(ad) if ctx_factory and ad is not None else None):
                y = self._attn_mlp_block(lp, x, causal=True, aux_sink=sink,
                                         collect_kv=collect_kv, **kw)
            return y, sink

        if cfg.scan_layers:
            def scan_body(x, xs):
                lp, ad = xs
                fn = jax.checkpoint(body) if cfg.remat else body
                return fn(x, lp, ad)

            xs = (layers, adapters)
            x, sinks = jax.lax.scan(scan_body, x, xs)
            # "__kv__" is the prefill capture: per-layer (k, v) rows stacked
            # along the scanned layer axis — passed through, never summed
            aux = {k: (v if k == "__kv__" else v.sum())
                   for k, v in sinks.items()} if sinks else {}
        else:
            n = cfg.num_layers
            kvs = []
            for i in range(n):
                x, sink = body(x, _slice_layer(layers, i),
                               _slice_layer(adapters, i) if adapters is not None else None)
                for k, v in sink.items():
                    if k == "__kv__":
                        kvs.append(v)
                    else:
                        aux[k] = aux.get(k, 0.0) + v
            if kvs:
                aux["__kv__"] = jax.tree.map(lambda *a: jnp.stack(a), *kvs)
        return x, aux

    # ---- hybrid: a period of layer kinds, repeated ----

    def _run_hybrid(self, params, x, adapters, ctx_factory, *, positions, segment_ids, reset):
        """Scan over the periods; each layer of a period is rematerialised on
        its own, so the backward holds one layer's internals at a time.  A
        layer takes the period's whole slice of the stacks and picks its own
        inside the checkpoint, so the scan's residuals are its inputs."""
        cfg = self.cfg
        ad = adapters if isinstance(adapters, dict) else {}
        shared, ad_shared = params.get("shared_attn"), ad.get("shared_attn")
        ad_blocks = {k: v for k, v in ad.items() if k != "shared_attn"}

        def layer_fn(mixer, group, i):
            def f(x, blocks, ad_blocks, shared, ad_shared):
                if i is None:
                    lp, a = shared, ad_shared
                else:
                    lp = _slice_layer(blocks[group], i)
                    a = _slice_layer(ad_blocks[group], i) if group in ad_blocks else None
                with adapter_scope(ctx_factory(a) if ctx_factory and a is not None else None):
                    if mixer == "mamba":
                        x, _ = self._mamba_layer(lp, x, reset=reset)
                    else:
                        x = self._attn_mlp_block(
                            lp, x, causal=True, positions=positions,
                            mrope_positions=None, segment_ids=segment_ids, aux_sink={},
                        )
                return x
            return jax.checkpoint(f) if cfg.remat else f

        layers = [layer_fn(*spec) for spec in self._period()]

        def period(x, blocks, ad_blocks):
            for f in layers:
                x = f(x, blocks, ad_blocks, shared, ad_shared)
            return x

        if cfg.scan_layers:
            x, _ = jax.lax.scan(lambda x, xs: (period(x, *xs), None), x,
                                (params["blocks"], ad_blocks))
        else:
            for p in range(cfg.n_periods):
                x = period(x, _slice_layer(params["blocks"], p),
                           _slice_layer(ad_blocks, p))
        return x, {}

    # ---- xlstm ----

    def _run_xlstm(self, params, x, adapters, ctx_factory, *, reset):
        cfg = self.cfg
        blocks = params["blocks"]
        ad_m = adapters.get("mlstm") if isinstance(adapters, dict) else None
        ad_s = adapters.get("slstm") if isinstance(adapters, dict) else None
        per = cfg.slstm_period - 1

        def super_block(x, xs):
            mb, sb, adm, ads = xs
            for i in range(per):
                lp = _slice_layer(mb, i)
                adi = _slice_layer(adm, i) if adm is not None else None
                with adapter_scope(ctx_factory(adi) if ctx_factory and adi is not None else None):
                    h = _apply_norm(lp["ln"], x, cfg.norm_eps)
                    y, _ = ssm.mlstm_apply(lp["mlstm"], h, cfg, reset=reset)
                x = shard(x + y, "batch", "seq", None)
            with adapter_scope(ctx_factory(ads) if ctx_factory and ads is not None else None):
                h = _apply_norm(sb["ln"], x, cfg.norm_eps)
                y, _ = ssm.slstm_apply(sb["slstm"], h, cfg)
            x = shard(x + y, "batch", "seq", None)
            return x, {}

        n_super = cfg.num_layers // cfg.slstm_period
        xs = (blocks["mlstm"], blocks["slstm"], ad_m, ad_s)
        if cfg.scan_layers:
            def scan_body(x, xs):
                fn = jax.checkpoint(super_block) if cfg.remat else super_block
                return fn(x, xs)
            x, _ = jax.lax.scan(scan_body, x, xs)
        else:
            for i in range(n_super):
                x, _ = super_block(x, jax.tree.map(lambda a: a[i] if a is not None else None, xs,
                                                   is_leaf=lambda v: v is None))
        return x, {}

    # ---- audio (whisper) ----

    def _encode_audio(self, params, audio_embed):
        cfg = self.cfg
        S = audio_embed.shape[1]
        pos = sinusoidal_positions(S, cfg.d_model).astype(audio_embed.dtype)
        x = shard(audio_embed + pos[None], "batch", "seq", None)

        def body(x, lp):
            h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
            a = attn.attention_apply(lp["attn"], h, cfg, mode=self.attn_mode, causal=False)
            x = shard(x + a, "batch", "seq", None)
            h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
            y = mlp_apply(lp["mlp"], h, cfg.gated_mlp)
            return shard(x + y, "batch", "seq", None), None

        if cfg.scan_layers:
            fn = jax.checkpoint(body) if cfg.remat else body
            x, _ = jax.lax.scan(fn, x, params["encoder"])
        else:
            for i in range(cfg.num_encoder_layers):
                x, _ = body(x, _slice_layer(params["encoder"], i))
        return _apply_norm(params["enc_final_norm"], x, cfg.norm_eps)

    def _forward_audio(self, params, batch, adapters, ctx_factory, return_logits):
        cfg = self.cfg
        enc = self._encode_audio(params, batch["audio_embed"])
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed_apply(params["embed"], tokens)
        pos = sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
        x = shard(x + pos[None], "batch", "seq", None)

        def body(x, lp, ad):
            with adapter_scope(ctx_factory(ad) if ctx_factory and ad is not None else None):
                h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
                a = attn.attention_apply(lp["attn"], h, cfg, mode=self.attn_mode, causal=True)
                x = shard(x + a, "batch", "seq", None)
                h = _apply_norm(lp["ln_cross"], x, cfg.norm_eps)
                kc = attn.attention_apply(
                    lp["cross"], h, cfg, mode=self.attn_mode,
                    kv_override=self._cross_kv(lp["cross"], enc),
                )
                x = shard(x + kc, "batch", "seq", None)
                h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
                y = mlp_apply(lp["mlp"], h, cfg.gated_mlp)
            return shard(x + y, "batch", "seq", None), None

        if cfg.scan_layers:
            def scan_body(x, xs):
                lp, ad = xs
                fn = jax.checkpoint(body) if cfg.remat else body
                return fn(x, lp, ad)
            x, _ = jax.lax.scan(scan_body, x, (params["layers"], adapters))
        else:
            for i in range(cfg.num_layers):
                x, _ = body(x, _slice_layer(params["layers"], i),
                            _slice_layer(adapters, i) if adapters is not None else None)

        x = _apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        out: Dict[str, Any] = {"aux": {}}
        if return_logits:
            out["logits"] = logits
        if "labels" in batch:
            out["per_token_loss"] = self._per_token_loss(logits, batch)
        return out

    @staticmethod
    def _cross_kv(p, enc):
        k = jnp.einsum("bsd,dhk->bshk", enc, p["w_k"])
        v = jnp.einsum("bsd,dhk->bshk", enc, p["w_v"])
        if "b_k" in p:
            k, v = k + p["b_k"], v + p["b_v"]
        return k, v

    # ------------------------------------------------------------------
    # Decode (serving)
    # ------------------------------------------------------------------

    def init_decode_state(
        self, params, batch: int, max_len: int, audio_embed: Optional[jax.Array] = None,
        cache_dtype=jnp.bfloat16, prefix_reserve: int = 0, per_row: bool = False,
    ) -> Dict[str, Any]:
        """Decode state; ``prefix_reserve=P`` grows every KV cache by ``P``
        leading rows where soft-prompt PEFT's learned k/v rows fold in at
        prefill/bind time (real tokens start at offset ``P``); ``per_row``
        makes ``pos`` a [B] vector so a fused request pool decodes rows at
        independent context lengths.  ``state["lo"]`` is each row's first
        valid cache index (``P`` minus that row's folded prefix length)."""
        cfg = self.cfg
        hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()
        cache_rows = prefix_reserve + max_len

        def kv(n):
            return {
                "k": jnp.zeros((n, batch, cache_rows, hkv, dh), cache_dtype),
                "v": jnp.zeros((n, batch, cache_rows, hkv, dh), cache_dtype),
            }

        state: Dict[str, Any] = {
            "pos": jnp.zeros((batch,) if per_row else (), jnp.int32)}
        if prefix_reserve or per_row:
            state["lo"] = jnp.full((batch,), prefix_reserve, jnp.int32)
        if cfg.family in ("dense", "vlm", "moe"):
            state["kv"] = kv(cfg.num_layers)
        elif cfg.family == "hybrid":
            # per kind, [period, index within the period, ...]
            n_per, pat = cfg.n_periods, cfg.layer_pattern
            ms = ssm.mamba2_init_state(cfg, batch)
            state["mamba"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_per, pat.count("mamba")) + a.shape), ms
            )
            n_attn = pat.count("attention")
            state["kv"] = jax.tree.map(lambda a: a.reshape((n_per, n_attn) + a.shape[1:]),
                                       kv(n_per * n_attn))
        elif cfg.family == "ssm":
            n_super = cfg.num_layers // cfg.slstm_period
            per = cfg.slstm_period - 1
            m0 = ssm.mlstm_init_state(cfg, batch)
            s0 = ssm.slstm_init_state(cfg, batch)
            state["mlstm"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_super, per) + a.shape), m0)
            state["slstm"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_super,) + a.shape), s0)
        elif cfg.family == "audio":
            state["kv"] = kv(cfg.num_layers)
            if audio_embed is not None:
                enc = self._encode_audio(params, audio_embed)
                ck = jax.vmap(lambda lp: self._cross_kv(lp, enc))(params["layers"]["cross"])
            else:  # no audio given: zeros cross-KV (a shape stand-in)
                src = cfg.max_source_positions
                ck = (
                    jnp.zeros((cfg.num_layers, batch, src, cfg.num_heads, dh), cache_dtype),
                    jnp.zeros((cfg.num_layers, batch, src, cfg.num_heads, dh), cache_dtype),
                )
            state["cross_k"], state["cross_v"] = ck[0].astype(cache_dtype), ck[1].astype(cache_dtype)
        return state

    def prefill(
        self, params, batch: Dict[str, jax.Array], state: Dict[str, Any],
        adapters: Any = None, ctx_factory: Optional[CtxFactory] = None,
        prefix_reserve: int = 0, lengths: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Chunked prompt processing INTO the decode KV cache.

        Runs the ordinary (adapter-aware) training forward over the prompt
        and captures every layer's post-RoPE k/v rows into ``state`` at
        offset ``prefix_reserve`` — the prefix-aware cache layout of
        ``init_decode_state``, whose reserved leading region the serving
        layer fills with soft-prompt rows at bind time.  ``lengths`` [B]
        gives each row's true prompt length (rows are padded to a common
        S); positions past a row's length hold junk that stays outside the
        valid cache window and is overwritten as decode advances.  Returns
        (logits over the prompt, updated state).  Dense/VLM/MoE families
        (full-depth KV stacks) only.
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "vlm", "moe"):
            raise NotImplementedError(
                f"prefill-into-cache supports dense/vlm/moe families, not "
                f"{cfg.family}; drive the prompt through decode_step instead")
        out = self.forward(params, batch, adapters=adapters,
                           ctx_factory=ctx_factory, return_logits=True,
                           collect_kv=True)
        ks, vs = out["kv"]  # [L, B, S, Hkv, dh]
        B, S = batch["tokens"].shape
        kc, vc = state["kv"]["k"], state["kv"]["v"]
        new_state = dict(state)
        new_state["kv"] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                kc, ks.astype(kc.dtype), prefix_reserve, axis=2),
            "v": jax.lax.dynamic_update_slice_in_dim(
                vc, vs.astype(vc.dtype), prefix_reserve, axis=2),
        }
        t = jnp.asarray(S, jnp.int32) if lengths is None else lengths.astype(jnp.int32)
        if state["pos"].ndim == 1:
            t = jnp.broadcast_to(t, (B,))
        new_state["pos"] = t
        return out["logits"], new_state

    def decode_step(
        self, params, state: Dict[str, Any], tokens: jax.Array,
        adapters: Any = None, ctx_factory: Optional[CtxFactory] = None,
        prefix_reserve: int = 0,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decode token for every row.  With ``adapters``/``ctx_factory``
        the step is fully task-aware: every family threads the per-layer
        adapter slice into the BaseOp hook scope, so all registered PEFT
        methods apply at decode exactly as at train time.  ``prefix_reserve``
        is the static prefix region of the cache layout (see
        ``init_decode_state``); ``state["pos"]`` counts REAL tokens."""
        cfg = self.cfg
        pos = state["pos"]  # [] or [B]: real-token count (RoPE position)
        lo = state.get("lo")  # [B] per-row cache-window start, or None
        x = self._embed(params, tokens)  # [B, 1, d]
        if cfg.family == "audio":
            max_len = state["kv"]["k"].shape[2]
            pe = sinusoidal_positions(max_len, cfg.d_model)  # static table
            pe_tok = jnp.take(pe, jnp.reshape(pos, (-1,)), axis=0)[:, None]
            x = x + pe_tok.astype(x.dtype)
        mrope = None
        if cfg.mrope:
            mrope = jnp.broadcast_to(
                jnp.reshape(pos, (1, -1, 1)), (3, tokens.shape[0], 1)
            ).astype(jnp.int32)

        def attn_cache(kc, vc):
            """Per-layer cache dict: write index = prefix_reserve + pos."""
            c = {"k": kc, "v": vc, "len": prefix_reserve + pos}
            if prefix_reserve or lo is not None:
                c["t"] = pos
            if lo is not None:
                c["lo"] = lo
            return c

        new_state = dict(state)

        if cfg.family in ("dense", "vlm", "moe"):
            def body(x, xs):
                lp, kc, vc, ad = xs
                with adapter_scope(ctx_factory(ad) if ctx_factory and ad is not None else None):
                    h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
                    a, cache = attn.attention_decode_apply(
                        lp["attn"], h, cfg, attn_cache(kc, vc), mrope_positions=mrope,
                    )
                    x = x + a
                    h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
                    if cfg.family == "moe" and "moe" in lp:
                        y, _ = moe_apply(lp["moe"], h, cfg)
                        if "shared_mlp" in lp:
                            y = y + mlp_apply(lp["shared_mlp"], h, cfg.gated_mlp, prefix="shared_mlp")
                    else:
                        y = mlp_apply(lp["mlp"], h, cfg.gated_mlp)
                return x + y, (cache["k"], cache["v"])

            xs = (params["layers"], state["kv"]["k"], state["kv"]["v"], adapters)
            x, (ks, vs) = _scan_or_loop(body, x, xs, cfg.num_layers, cfg.scan_layers)
            new_state["kv"] = {"k": ks, "v": vs}

        elif cfg.family == "hybrid":
            ad = adapters if isinstance(adapters, dict) else {}
            shared, ad_shared = params.get("shared_attn"), ad.get("shared_attn")

            def period_body(x, xs):
                blocks, ad_blocks, mstate, kc, vc = xs
                mst, ks, vs = [], [], []
                for mixer, group, i in self._period():
                    if i is None:
                        lp, a = shared, ad_shared
                    else:
                        lp = _slice_layer(blocks[group], i)
                        a = _slice_layer(ad_blocks[group], i) if group in ad_blocks else None
                    with adapter_scope(ctx_factory(a) if ctx_factory and a is not None else None):
                        if mixer == "mamba":
                            x, st = self._mamba_layer(lp, x, state=_slice_layer(mstate, i))
                            mst.append(st)
                            continue
                        j = len(ks)
                        h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
                        y, cache = attn.attention_decode_apply(
                            lp["attn"], h, cfg, attn_cache(kc[j], vc[j]))
                        x = x + self._res(y)
                        h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
                        x = x + self._res(mlp_apply(lp["mlp"], h, cfg.gated_mlp))
                    ks.append(cache["k"])
                    vs.append(cache["v"])
                stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
                return x, (stack(mst), stack(ks), stack(vs))

            ad_blocks = {k: v for k, v in ad.items() if k != "shared_attn"}
            xs = (params["blocks"], ad_blocks, state["mamba"],
                  state["kv"]["k"], state["kv"]["v"])
            x, (mst, ks, vs) = _scan_or_loop(period_body, x, xs, cfg.n_periods,
                                             cfg.scan_layers)
            new_state["mamba"] = mst
            new_state["kv"] = {"k": ks, "v": vs}

        elif cfg.family == "ssm":
            per = cfg.slstm_period - 1
            ad_m = adapters.get("mlstm") if isinstance(adapters, dict) else None
            ad_s = adapters.get("slstm") if isinstance(adapters, dict) else None

            def super_body(x, xs):
                mb, sb, mstate, sstate, adm, ads = xs
                msts = []
                for i in range(per):
                    lp = _slice_layer(mb, i)
                    st = _slice_layer(mstate, i)
                    adi = _slice_layer(adm, i) if adm is not None else None
                    with adapter_scope(ctx_factory(adi) if ctx_factory and adi is not None else None):
                        h = _apply_norm(lp["ln"], x, cfg.norm_eps)
                        y, st2 = ssm.mlstm_apply(lp["mlstm"], h, cfg, state=st)
                    msts.append(st2)
                    x = x + y
                with adapter_scope(ctx_factory(ads) if ctx_factory and ads is not None else None):
                    h = _apply_norm(sb["ln"], x, cfg.norm_eps)
                    y, sst2 = ssm.slstm_apply(sb["slstm"], h, cfg, state=sstate)
                x = x + y
                return x, (jax.tree.map(lambda *a: jnp.stack(a), *msts), sst2)

            xs = (params["blocks"]["mlstm"], params["blocks"]["slstm"],
                  state["mlstm"], state["slstm"], ad_m, ad_s)
            n_super = cfg.num_layers // cfg.slstm_period
            x, (mst, sst) = _scan_or_loop(super_body, x, xs, n_super, cfg.scan_layers)
            new_state["mlstm"], new_state["slstm"] = mst, sst

        elif cfg.family == "audio":
            def body(x, xs):
                lp, kc, vc, ck, cv, ad = xs
                with adapter_scope(ctx_factory(ad) if ctx_factory and ad is not None else None):
                    h = _apply_norm(lp["ln1"], x, cfg.norm_eps)
                    a, cache = attn.attention_decode_apply(
                        lp["attn"], h, cfg, attn_cache(kc, vc))
                    x = x + a
                    h = _apply_norm(lp["ln_cross"], x, cfg.norm_eps)
                    c, _ = attn.attention_decode_apply(
                        lp["cross"], h, cfg,
                        {"k": ck, "v": cv, "len": jnp.asarray(ck.shape[1], jnp.int32)},
                        update_cache=False,
                    )
                    x = x + c
                    h = _apply_norm(lp["ln2"], x, cfg.norm_eps)
                    x = x + mlp_apply(lp["mlp"], h, cfg.gated_mlp)
                return x, (cache["k"], cache["v"])

            xs = (params["layers"], state["kv"]["k"], state["kv"]["v"],
                  state["cross_k"], state["cross_v"], adapters)
            x, (ks, vs) = _scan_or_loop(body, x, xs, cfg.num_layers, cfg.scan_layers)
            new_state["kv"] = {"k": ks, "v": vs}

        x = _apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        new_state["pos"] = pos + 1
        return logits, new_state


def build_model(cfg: ArchConfig, tp_size: int = 1) -> Model:
    """Pick the attention sharding mode for the given TP degree (DESIGN §5)."""
    if cfg.attention == "none":
        return Model(cfg, attn_mode="pairs")
    mode = "pairs" if (tp_size <= 1 or cfg.num_heads % tp_size == 0) else "kvscan"
    return Model(cfg, attn_mode=mode)
