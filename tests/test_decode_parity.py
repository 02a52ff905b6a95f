"""Decode <-> forward parity: the serve path must reproduce the training
forward's logits token-by-token — the strongest cross-path correctness
check (covers KV caches, SSM states, conv buffers, positional handling)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.transformer import build_model

# representative member of each decode-state family
PARITY_ARCHS = ["llama3.2-3b", "deepseek-moe-16b", "zamba2-2.7b",
                "xlstm-1.3b", "whisper-large-v3", "qwen2-vl-7b"]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_decode_matches_forward_logits(arch, key):
    cfg = smoke_config(arch)
    m = build_model(cfg)
    params = m.init(key)
    B, S = 2, 8
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.mrope:
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (3, B, S))
    if cfg.family == "audio":
        batch["audio_embed"] = jax.random.normal(
            key, (B, cfg.max_source_positions, cfg.d_model), jnp.bfloat16)

    fwd = m.forward(params, batch, return_logits=True)["logits"]

    st = m.init_decode_state(params, B, S + 1,
                             audio_embed=batch.get("audio_embed"),
                             cache_dtype=jnp.float32)
    dec = []
    for t in range(S):
        logits, st = m.decode_step(params, st, tokens[:, t:t + 1])
        dec.append(logits[:, 0])
    dec = jnp.stack(dec, axis=1)

    f = np.asarray(fwd, np.float32)
    d = np.asarray(dec, np.float32)
    # compare softmax distributions (logits match up to bf16 accumulation)
    pf = jax.nn.softmax(f, axis=-1)
    pd = jax.nn.softmax(d, axis=-1)
    err = float(np.max(np.abs(np.asarray(pf) - np.asarray(pd))))
    assert err < 0.08, f"{arch}: decode/forward prob divergence {err}"
    # argmax agreement on the vast majority of positions
    agree = float(np.mean(np.argmax(f, -1) == np.argmax(d, -1)))
    assert agree > 0.85, f"{arch}: argmax agreement {agree}"


def test_rules_matrix_cells_valid():
    """rules_for() yields a consistent spec for every (arch x shape) cell:
    all rule targets reference real mesh axes (the dry-run's contract)."""
    from repro.configs import ARCH_NAMES, SHAPES, dryrun_cells, get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.rules import attn_mode_for, rules_for

    mesh = make_mesh((1, 1), ("data", "model"))
    seen = 0
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shp in dryrun_cells(arch):
            shape = SHAPES[shp]
            r = rules_for(cfg, shape, mesh)
            mode = attn_mode_for(cfg, mesh)
            assert mode in ("pairs", "kvscan")
            # every target is None / axis name / tuple of axis names / flag
            for k, v in r.rules:
                if v is None or k in r.FLAG_KEYS:
                    continue
                tgt = (v,) if isinstance(v, str) else v
                for a in tgt:
                    assert a in ("pod", "data", "model"), (arch, shp, k, v)
            seen += 1
    assert seen == 36  # 8 archs x 3 + 3 archs x 4


def test_cluster_simulator_policies():
    from repro.cluster import ClusterSim, philly_style_trace

    trace = philly_style_trace(horizon_min=12 * 60, seed=1)
    assert len(trace) > 100
    base = ClusterSim(multiplexed=False, max_colocate=1).run(trace)
    mux_fcfs = ClusterSim(multiplexed=True, max_colocate=8, policy="fcfs").run(trace)
    mux_bf = ClusterSim(multiplexed=True, max_colocate=8, policy="best_fit").run(trace)
    # multiplexing strictly improves served work and admission
    assert mux_fcfs["served_task_min"] > base["served_task_min"]
    assert mux_fcfs["admission_rate"] >= base["admission_rate"]
    # best-fit packs at least as much as fcfs
    assert mux_bf["served_task_min"] >= 0.9 * mux_fcfs["served_task_min"]
    # work conservation: completed + dropped == arrivals
    for r in (base, mux_fcfs, mux_bf):
        assert r["completed"] + r["dropped"] == len(trace)
