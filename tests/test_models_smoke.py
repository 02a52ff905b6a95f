"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step on CPU, output shapes + no NaNs + decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.models.transformer import build_model
from repro.peft.adapters import LORA
from repro.peft.methods import AdapterConfig
from repro.peft.multitask import MultiTaskAdapters, TaskSegments


def _batch(cfg, key, B=2, S=32):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.mrope:
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (3, B, S))
    if cfg.family == "audio":
        batch["audio_embed"] = jax.random.normal(
            key, (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_forward_and_decode(arch, key):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = _batch(cfg, key)
    out = m.forward(params, batch, return_logits=True)
    B, S = batch["tokens"].shape
    assert out["logits"].shape[:2] == (B, S)
    loss = float(out["per_token_loss"].mean())
    assert np.isfinite(loss), f"{arch} loss={loss}"

    st = m.init_decode_state(params, B, 16, audio_embed=batch.get("audio_embed"))
    tok = batch["tokens"][:, :1]
    for _ in range(2):
        logits, st = m.decode_step(params, st, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    assert int(st["pos"]) == 2


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_train_step_with_adapters(arch, key):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    batch = _batch(cfg, key)
    mta = MultiTaskAdapters(cfg, [AdapterConfig(LORA, rank=4), AdapterConfig(LORA, rank=4)])
    seg = TaskSegments.contiguous([1, 1])
    ad = mta.init(jax.random.PRNGKey(1))
    ctxf = mta.ctx_factory(seg)

    def loss_fn(ad):
        out = m.forward(params, batch, adapters=ad, ctx_factory=ctxf)
        return seg.per_task_loss(out["per_token_loss"], batch["loss_mask"]).sum()

    loss, grads = jax.value_and_grad(loss_fn, allow_int=True)(ad)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(
        float(jnp.abs(g.astype(jnp.float32)).sum())
        for g in jax.tree.leaves(grads)
        if hasattr(g, "dtype") and g.dtype != jax.dtypes.float0
    )
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: no adapter gradient signal"


def test_param_counts_match_configs():
    """Backbone param counts are in the right ballpark for the named sizes."""
    expect = {
        "yi-34b": 34e9, "llama3.2-3b": 3.2e9, "starcoder2-7b": 7e9,
        "smollm-360m": 0.36e9, "qwen2-vl-7b": 7.6e9,
        "deepseek-moe-16b": 16.4e9, "qwen3-moe-235b-a22b": 235e9,
        "zamba2-2.7b": 2.7e9, "xlstm-1.3b": 1.3e9, "whisper-large-v3": 1.5e9,
    }
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert 0.5 * n < got < 1.8 * n, f"{arch}: {got/1e9:.2f}B vs {n/1e9:.2f}B"


def test_moe_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    active = cfg.param_count(active_only=True)
    assert 10e9 < active < 40e9  # ~22B active
