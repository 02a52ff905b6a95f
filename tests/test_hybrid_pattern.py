"""Hybrid backbones as a period of layer kinds (``ArchConfig.layer_pattern``):
granite-4.0-h-micro's layout (every layer a mixer plus its own MLP, per-layer
remat, the adapter stacks by kind), its parameter count, and the mechanisms it
adds to the model: the Mamba-2 conv bias, attention without positions and a
given softmax scale (on every kernel tier), and the embedding, residual and
logits multipliers.  Zamba2's weight-shared block is the same path with one
shared attention block; its own tests stay in test_decode_parity and
test_kernel_grads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models import ssm
from repro.models.transformer import build_model
from repro.peft.methods import AdapterConfig
from repro.peft.multitask import MultiTaskAdapters

GRANITE = "granite-4.0-h-micro"


def tiny_granite(**over):
    """One period of ``[mamba, mamba, attention, mamba]`` at small widths."""
    base = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
                head_dim=16, d_ff=128, vocab_size=256, ssm_state=16,
                ssm_head_dim=16, ssm_expand=1, ssm_chunk=16,
                layer_pattern=("mamba", "mamba", "attention", "mamba"),
                attention_scale=1.0 / 16, attn_q_block=32, attn_kv_block=32)
    base.update(over)
    return get_config(GRANITE).with_overrides(**base)


def test_granite_parameter_count():
    """3.19 B as published: a Mamba layer 76.2 M, an attention layer 60.8 M
    (each with its own MLP), the tied embedding 205.5 M."""
    cfg = get_config(GRANITE)
    n = cfg.param_count()
    assert abs(n - 3.19e9) <= 0.005 * 3.19e9, n
    assert cfg.layers_of("mamba") == 36 and cfg.layers_of("attention") == 4
    tree = jax.tree.leaves(build_model(cfg).abstract_params())
    assert sum(int(np.prod(a.shape)) for a in tree) == n  # the model's own
    assert cfg.model_flops(1) == 6.0 * n


def test_granite_layout_is_by_kind_and_period():
    cfg = get_config(GRANITE)
    p = build_model(cfg).abstract_params()
    mamba, attn_ = p["blocks"]["mamba"], p["blocks"]["attn"]
    assert mamba["mamba"]["w_in"].shape == (4, 9, 2048, 2 * 4096 + 2 * 128 + 64)
    assert mamba["mamba"]["conv_b"].shape == (4, 9, 4096 + 2 * 128)
    assert mamba["mlp"]["w_up"].shape == (4, 9, 2048, 8192)
    assert attn_["attn"]["w_q"].shape == (4, 1, 2048, 32, 64)
    assert attn_["mlp"]["w_down"].shape == (4, 1, 8192, 2048)
    assert "shared_attn" not in p
    z = build_model(get_config("zamba2-2.7b")).abstract_params()
    assert z["blocks"]["mamba"]["mamba"]["w_in"].shape[:2] == (9, 5)
    assert "mlp" not in z["blocks"]["mamba"] and "shared_attn" in z


def test_adapter_stacks_follow_the_pattern():
    cfg = tiny_granite()
    sites = ("ssm_in", "ssm_out", "attn_q", "attn_v", "mlp_up")
    mta = MultiTaskAdapters(cfg, [AdapterConfig("lora", rank=4, targets=sites)])
    spec = jax.eval_shape(lambda: mta.init(jax.random.PRNGKey(0)))
    m, a = spec["mamba"]["lora"], spec["attn"]["lora"]
    assert set(m) == {"ssm_in", "ssm_out", "mlp_up"}
    assert set(a) == {"attn_q", "attn_v", "mlp_up"}
    assert m["ssm_in"]["a"].shape[:3] == (1, 3, mta.kind_capacity["lora"])
    assert a["attn_q"]["a"].shape[:2] == (1, 1)
    z = MultiTaskAdapters(smoke_config("zamba2-2.7b"),
                          [AdapterConfig("lora", rank=4, targets=sites)])
    zs = jax.eval_shape(lambda: z.init(jax.random.PRNGKey(0)))
    assert set(zs["mamba"]["lora"]) == {"ssm_in", "ssm_out"}
    assert set(zs["shared_attn"]["lora"]) == {"attn_q", "attn_v", "mlp_up"}


def _scan_body_remats(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 32), jnp.int32)

    def loss(p):
        out = model.forward(p, {"tokens": tokens, "labels": tokens})
        return out["per_token_loss"].sum()

    jaxpr = jax.make_jaxpr(loss)(params)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    body = scans[0].params["jaxpr"].jaxpr
    return sum(1 for e in body.eqns if e.primitive.name.startswith("remat"))


def test_each_layer_is_rematerialised_on_its_own():
    """The scan over periods runs one checkpoint per layer of the period,
    not one around the whole period."""
    cfg = tiny_granite(scan_layers=True, remat=True)
    assert _scan_body_remats(cfg) == len(cfg.layer_pattern)


def test_conv_bias_enters_the_conv():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    np.testing.assert_allclose(ssm._causal_depthwise_conv(x, w, b),
                               ssm._causal_depthwise_conv(x, w) + b, rtol=1e-5, atol=1e-6)
    spec = ssm.mamba2_spec(tiny_granite())
    assert spec["conv_b"].shape == spec["conv"].shape[1:]
    assert "conv_b" not in ssm.mamba2_spec(smoke_config("zamba2-2.7b"))


def test_nope_projects_without_rotation():
    cfg = tiny_granite()
    p = build_model(cfg).init(jax.random.PRNGKey(0))["blocks"]["attn"]
    lp = jax.tree.map(lambda a: a[0, 0], p)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64), jnp.bfloat16)
    pos = jnp.arange(8)[None] + 5
    q, k, _ = attn._project_qkv(lp, x, cfg, pos, None)
    np.testing.assert_array_equal(q, jnp.einsum("bsd,dhk->bshk", x, lp["w_q"]))
    q_rope, _, _ = attn._project_qkv(lp, x, cfg.with_overrides(positional="rope"),
                                     pos, None)
    assert not np.allclose(np.asarray(q_rope, np.float32), np.asarray(q, np.float32))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_softmax_scale_reaches_every_tier(impl):
    """``packed_attention`` with a given scale: the same values and
    gradients on the kernel tiers as the xla flash path, and the same as
    pre-scaling q under the default scale."""
    B, S, H, Hkv, dh = 1, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    g = jax.random.normal(ks[3], (B, S, H, dh))
    c = 1.0 / 16

    def loss(q, k, v, scale):
        return jnp.sum(kops.packed_attention(q, k, v, block_q=32, scale=scale) * g)

    prev = kops.get_impl()
    kops.set_impl(impl)
    try:
        got = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, c)
        default = loss(q, k, v, 1 / np.sqrt(dh))
    finally:
        kops.set_impl(prev)
    kops.set_impl("xla")
    try:
        want = jax.value_and_grad(
            lambda q, k, v: loss(q * (c * np.sqrt(dh)), k, v, 1 / np.sqrt(dh)),
            argnums=(0, 1, 2))(q, k, v)
    finally:
        kops.set_impl(prev)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    assert abs(float(default) - float(got[0])) > 1e-3


def test_multipliers_scale_the_stream():
    """Embedding x m, residual branches x r and logits / s, each against the
    same model with that multiplier at 1."""
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 32), 0, 256)
    plain = tiny_granite(embedding_multiplier=1.0, residual_multiplier=1.0,
                         logits_scaling=1.0)
    m = build_model(plain)
    params = m.init(jax.random.PRNGKey(0))

    def logits(cfg):
        return build_model(cfg).forward(params, {"tokens": tokens},
                                        return_logits=True)["logits"]

    base = logits(plain)
    np.testing.assert_array_equal(logits(plain.with_overrides(logits_scaling=8.0)),
                                  base / 8)
    for over in ({"embedding_multiplier": 12.0}, {"residual_multiplier": 0.22}):
        assert not np.allclose(np.asarray(logits(plain.with_overrides(**over)),
                                          np.float32), np.asarray(base, np.float32))
    x = m._embed(params, tokens)
    x12 = build_model(plain.with_overrides(embedding_multiplier=12.0))._embed(
        params, tokens)
    np.testing.assert_array_equal(x12, x * jnp.asarray(12.0, x.dtype))


def test_residual_multiplier_is_the_published_number():
    """A bfloat16 branch is scaled by 0.22 itself, in float32, and rounded
    once: the multiplier rounded to bfloat16 (0.2197) would shorten every
    residual branch by 0.12%, a bias the benchmark's reference check reads
    in the gradients."""
    m = build_model(tiny_granite(residual_multiplier=0.22))
    y = jax.random.normal(jax.random.PRNGKey(5), (4, 256, 256), jnp.bfloat16)
    out = m._res(y)
    np.testing.assert_array_equal(
        out, (y.astype(jnp.float32) * 0.22).astype(jnp.bfloat16))
    assert not np.array_equal(out, y * jnp.asarray(0.22, jnp.bfloat16))
