"""Architecture configs and input-shape registry.

Every assigned architecture has one ``<arch>.py`` in this package exporting
``CONFIG: ArchConfig``.  ``get_config(name)`` resolves by registry id, and
``SHAPES`` holds the assigned input-shape set (shared by all LM archs).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

#: taps of the Mamba-2 depthwise causal convolution
CONV_W = 4

# ---------------------------------------------------------------------------
# Shape registry (assigned: 4 shapes per LM arch)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input shape.

    ``kind`` selects which step is lowered for the dry-run:
      * ``train``   -> ``train_step`` (fwd + adapter-grad bwd + optimizer)
      * ``prefill`` -> ``prefill_step`` (forward, logits, no bwd)
      * ``decode``  -> ``serve_step``  (one new token over a KV cache of
                        ``seq_len``)
    """

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    """A backbone architecture, parameterized to cover the assigned pool.

    ``family`` in {dense, moe, hybrid, ssm, vlm, audio}.  The model zoo
    (``repro.models``) assembles blocks from these fields; the same config
    object also drives sharding rules and the dry-run input specs.
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    router_aux_coef: float = 0.001
    capacity_factor: float = 1.25

    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    # hybrid: one period of layer kinds ("mamba" | "attention"), repeated
    # num_layers / len(layer_pattern) times.
    layer_pattern: Tuple[str, ...] = ()
    # zamba2: every attention layer runs one weight-shared attention+MLP block
    shared_attention: bool = False
    ssm_conv_bias: bool = False
    # xLSTM-style pattern: number of mLSTM layers per sLSTM layer (0 = none)
    slstm_period: int = 0

    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    max_source_positions: int = 0  # whisper: 1500 frames

    # --- positional / misc ---
    positional: str = "rope"  # "rope" | "nope" (attention sees no positions)
    rope_theta: float = 10_000.0
    mrope: bool = False  # qwen2-vl 3-section multimodal RoPE
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    gated_mlp: bool = True  # SwiGLU-style (gate/up/down); False -> fc1/fc2
    attention_bias: bool = False
    qk_norm: bool = False  # qwen3-style per-head RMSNorm on q/k

    # --- attention kind: "full" | "none" (pure recurrent) ---
    attention: str = "full"
    attention_scale: float = 0.0  # softmax scale; 0 -> 1 / sqrt(head_dim)

    # --- granite-style multipliers (1.0 = none) ---
    embedding_multiplier: float = 1.0  # scales the token embeddings
    residual_multiplier: float = 1.0  # scales each sublayer's residual branch
    logits_scaling: float = 1.0  # the logits are divided by it

    # --- dtype / execution knobs ---
    param_dtype: str = "bfloat16"
    # Frozen-backbone storage precision ("bfloat16" | "float32" | "int8").
    # "int8" quantizes every adapter-capable BaseOp weight at model build
    # (symmetric, per-output-channel scale) with dequant fused into the
    # hot-path kernels — see repro.models.quantize / kernels.quant_matmul.
    backbone_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    use_pallas: bool = False  # TPU target path; CPU dry-run uses jnp flash
    attn_q_block: int = 512
    attn_kv_block: int = 1024

    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    def softmax_scale(self) -> float:
        """The attention softmax scale: ``attention_scale`` where the
        configuration gives one, else ``1 / sqrt(head_dim)``."""
        return self.attention_scale or 1.0 / np.sqrt(self.resolved_head_dim())

    def backbone_dtype_bytes(self) -> int:
        """Bytes per resident backbone weight — the precision axis of the
        cost model (Eq. 5 / bandwidth terms) and of admission packing."""
        return {"int8": 1, "float8": 1, "bfloat16": 2, "float16": 2,
                "float32": 4}[self.backbone_dtype]

    @property
    def n_periods(self) -> int:
        """Repeats of ``layer_pattern`` (hybrid)."""
        return self.num_layers // len(self.layer_pattern)

    @property
    def mamba_mlp(self) -> bool:
        """Each Mamba layer carries its own MLP (hybrid) unless the MLP sits
        in zamba2's weight-shared attention block."""
        return not self.shared_attention

    def layers_of(self, kind: str) -> int:
        """Layers of one kind over the whole depth (hybrid)."""
        return self.n_periods * self.layer_pattern.count(kind)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim()

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim()

    def with_overrides(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (used for MODEL_FLOPS and memory model) ----
    def param_count(self, active_only: bool = False) -> int:
        """Backbone parameter count; ``active_only`` counts MoE active path."""
        d, hd = self.d_model, self.resolved_head_dim()
        n_attn = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
        if self.qk_norm:
            n_attn += 2 * hd
        if self.gated_mlp:
            n_mlp_dense = 3 * d * self.d_ff
        else:
            n_mlp_dense = 2 * d * self.d_ff
        n_norms = 2 * d

        def expert_params(n_experts: int) -> int:
            per = 3 * d * self.expert_d_ff if self.gated_mlp else 2 * d * self.expert_d_ff
            return n_experts * per

        total = 0
        if self.family in ("dense", "vlm"):
            total = self.num_layers * (n_attn + n_mlp_dense + n_norms)
        elif self.family == "moe":
            router = d * self.num_experts
            n_e = self.top_k if active_only else self.num_experts
            per_layer = (
                n_attn
                + expert_params(n_e)
                + expert_params(self.num_shared_experts)
                + router
                + n_norms
            )
            total = self.num_layers * per_layer
        elif self.family == "hybrid":
            d_in = self.ssm_expand * d
            nh = d_in // self.ssm_head_dim
            st = self.ssm_state
            n_ssm = d * (2 * d_in + 2 * st + nh)  # in-proj: z, x, B, C, dt
            n_ssm += CONV_W * (d_in + 2 * st)  # depthwise conv over x, B, C
            if self.ssm_conv_bias:
                n_ssm += d_in + 2 * st
            n_ssm += 3 * nh  # dt bias, A, D
            n_ssm += d_in  # gated RMSNorm
            n_ssm += d_in * d  # out-proj
            per_ssm = n_ssm + d
            if self.mamba_mlp:
                per_ssm += n_mlp_dense + d
            attn_copies = 1 if self.shared_attention else self.layers_of("attention")
            total = (self.layers_of("mamba") * per_ssm
                     + attn_copies * (n_attn + n_mlp_dense + n_norms))
        elif self.family == "ssm":  # xlstm: mLSTM/sLSTM projections, no d_ff MLP
            total = self.num_layers * (n_attn + n_norms)
        elif self.family == "audio":
            enc = self.num_encoder_layers * (n_attn + n_mlp_dense + n_norms)
            dec = self.num_layers * (2 * n_attn + n_mlp_dense + 3 * d)
            total = enc + dec
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return total + embed + d  # final norm

    def model_flops(self, tokens: int, active_only: bool = True, train: bool = True) -> float:
        """Standard 6*N*D (training) or 2*N*D (inference fwd) model FLOPs."""
        n = self.param_count(active_only=active_only)
        return (6.0 if train else 2.0) * n * tokens


_REGISTRY = {
    "qwen2-vl-7b": "qwen2_vl_7b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "yi-34b": "yi_34b",
    "llama3.2-3b": "llama3_2_3b",
    "starcoder2-7b": "starcoder2_7b",
    "smollm-360m": "smollm_360m",
    "zamba2-2.7b": "zamba2_2_7b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    "xlstm-1.3b": "xlstm_1_3b",
    "whisper-large-v3": "whisper_large_v3",
}

ARCH_NAMES = tuple(_REGISTRY)


def get_config(name: str) -> ArchConfig:
    mod_name = _REGISTRY.get(name, name.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def smoke_config(name: str) -> ArchConfig:
    """A reduced config of the same family for CPU smoke tests."""
    cfg = get_config(name)
    over = dict(
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        attn_q_block=32,
        attn_kv_block=32,
        scan_layers=False,
        remat=False,
    )
    if cfg.family == "moe":
        over.update(num_experts=4, top_k=2, expert_d_ff=32,
                    num_shared_experts=min(cfg.num_shared_experts, 1))
    if cfg.family in ("hybrid", "ssm"):
        over.update(ssm_state=8, ssm_head_dim=16, ssm_chunk=16, num_layers=4)
        if cfg.layer_pattern:
            over.update(layer_pattern=("mamba", "attention"))
        if cfg.slstm_period:
            over.update(slstm_period=2)
    if cfg.is_encoder_decoder:
        over.update(num_encoder_layers=2, max_source_positions=16)
    if cfg.family == "vlm":
        over.update(mrope_sections=(2, 3, 3))  # sums to head_dim/2 = 8
    return cfg.with_overrides(**over)


def dryrun_cells(arch: str) -> list[str]:
    """Which shapes the dry-run exercises for this arch (per DESIGN.md)."""
    cfg = get_config(arch)
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    # long_500k requires sub-quadratic attention: SSM / hybrid only.
    if cfg.family in ("ssm", "hybrid"):
        cells.append("long_500k")
    return cells
