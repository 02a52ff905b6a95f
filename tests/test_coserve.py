"""SLO co-serving acceptance: the task-aware decode path and its service.

Four guarantees:

  (a) DECODE PARITY, every registered PEFT method: a fused multi-task decode
      batch (one row per method, traced slot routing) produces bit-matching
      logits with each method decoded solo, AND each row's decode logits
      match the train-path forward (packed_attention / grouped kernels) —
      prefix-tuning via its k/v rows FOLDED into the KV cache (vs the train
      path's online-softmax carry).
  (b) striped-CP attention handles prefix rows (CP-aware prefix broadcast)
      instead of raising.
  (c) The pool data plane (bind = single-row prefill + prefix fold + scatter;
      greedy generation on device) reproduces the train-path greedy
      trajectory for both a reparameterized and a soft-prompt tenant.
  (d) Service-level co-serving: training losses with decode traffic
      interleaved match the no-decode run (rtol 2e-4), requests complete,
      and decode p50/p99 are recorded.  Auto-recalibration fires on drift.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.task import ParallelismSpec
from repro.data.synthetic import make_task
from repro.models.transformer import build_model
from repro.peft.methods import AdapterConfig
from repro.peft.methods import get_method, method_names
from repro.peft.multitask import MultiTaskAdapters, TaskSegments
from repro.serve import CoServeConfig, MuxTuneService

CFG = smoke_config("llama3.2-3b")


def _fold_prefix_rows(cfg, mta, params, state, row, task, pres):
    """Test-local mirror of the bind step's prefix KV fold: write the task's
    learned k/v rows right-aligned into the reserved cache region and open
    the row's window over them."""
    kind = mta.task_cfgs[task].kind
    if not get_method(kind).uses_attention_prefix:
        return state
    slot = int(mta.task_slot[task])
    pk = np.asarray(params[kind]["attn_prefix"]["pk"][:, slot], np.float32)
    pv = np.asarray(params[kind]["attn_prefix"]["pv"][:, slot], np.float32)
    L, P, kvd = pk.shape
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()
    k = state["kv"]["k"].at[:, row, pres - P:pres].set(
        jnp.asarray(pk.reshape(L, P, hkv, dh), state["kv"]["k"].dtype))
    v = state["kv"]["v"].at[:, row, pres - P:pres].set(
        jnp.asarray(pv.reshape(L, P, hkv, dh), state["kv"]["v"].dtype))
    state = dict(state)
    state["kv"] = {"k": k, "v": v}
    state["lo"] = state["lo"].at[row].set(pres - P)
    return state


def test_decode_parity_all_registered_methods(key):
    """(a): fused multi-task decode == solo decode == train-path forward,
    for EVERY method in the registry (prefix via KV fold-in)."""
    methods = method_names()
    cfg = CFG
    model = build_model(cfg)
    backbone = model.init(key)
    cfgs = [AdapterConfig(kind, rank=4) for kind in methods]
    mta = MultiTaskAdapters(cfg, cfgs)
    params = mta.init(jax.random.PRNGKey(1))
    n = len(methods)
    S = 5
    tokens = np.asarray(
        jax.random.randint(key, (S,), 1, cfg.vocab_size), np.int32)
    from repro.launch.steps import decode_prefix_reserve

    pres = decode_prefix_reserve(mta)
    assert pres > 0  # the registry includes prefix-tuning

    def decode_traj(row_task):
        """Teacher-forced decode logits [B, S, V] for a row->task map."""
        B = len(row_task)
        state = model.init_decode_state(None, B, S + 1, cache_dtype=jnp.float32,
                                        prefix_reserve=pres, per_row=True)
        for r, t in enumerate(row_task):
            state = _fold_prefix_rows(cfg, mta, params, state, r, t, pres)
        slots = {k: jnp.asarray(v)
                 for k, v in mta.decode_row_slots(row_task).items()}
        ctxf = mta.ctx_factory_from_slots(slots)

        @jax.jit
        def step(st, tok):
            return model.decode_step(backbone, st, tok, adapters=params,
                                     ctx_factory=ctxf, prefix_reserve=pres)

        out = []
        for s in range(S):
            tok = jnp.broadcast_to(jnp.asarray(tokens[s]), (B, 1))
            logits, state = step(state, tok)
            out.append(np.asarray(logits[:, 0], np.float32))
        return np.stack(out, axis=1)  # [B, S, V]

    fused = decode_traj(list(range(n)))
    for t, kind in enumerate(methods):
        solo = decode_traj([t])
        np.testing.assert_allclose(
            fused[t], solo[0], rtol=2e-4, atol=2e-4,
            err_msg=f"{kind}: fused decode != solo decode")
        # train-path reference: same tokens through the training forward
        ctxf = mta.ctx_factory(TaskSegments((t,), n))
        out = model.forward(backbone, {"tokens": jnp.asarray(tokens[None])},
                            adapters=params, ctx_factory=ctxf,
                            return_logits=True)
        ref = np.asarray(out["logits"], np.float32)[0]
        pf = jax.nn.softmax(ref, axis=-1)
        pd = jax.nn.softmax(fused[t], axis=-1)
        err = float(np.max(np.abs(np.asarray(pf) - np.asarray(pd))))
        assert err < 0.05, f"{kind}: decode/train prob divergence {err}"
        agree = float(np.mean(ref.argmax(-1) == fused[t].argmax(-1)))
        assert agree == 1.0, f"{kind}: argmax disagreement ({agree})"


def test_init_kv_cache_prefix_layout_matches_train_path(key):
    """The single-layer reference constructor (`init_kv_cache`) feeds
    `attention_decode_apply` directly: a prefix-aware per-row cache decoded
    token-by-token must reproduce the train-path attention (prefix rows via
    the online-softmax carry) — pins the layout contract (`len` pre-offset,
    `t` RoPE count, right-aligned fold, `lo` window) with a real consumer."""
    from repro.models.attention import (attention_apply,
                                        attention_decode_apply, init_kv_cache)
    from repro.models.layers import materialize

    cfg = CFG
    from repro.models import attention as attn_mod

    p = materialize(attn_mod.attention_spec(cfg), key)
    B, S, P, pres = 2, 6, 3, 4
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model),
                          jnp.float32)
    pk = jax.random.normal(jax.random.fold_in(key, 2), (B, P, hkv, dh),
                           jnp.float32) * 0.1
    pv = jax.random.normal(jax.random.fold_in(key, 3), (B, P, hkv, dh),
                           jnp.float32) * 0.1
    keep = jnp.asarray([[1.0] * P, [0.0] * P])  # row 1 owns no prefix
    from repro.models.attention import flash_attention_pairs

    # train path: full-sequence flash attention with the prefix carry
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = attn_mod._project_qkv(p, x, cfg, pos, None)
    ref = flash_attention_pairs(q, k, v, block=4, causal=True, positions=pos,
                                kv_prefix=(pk, pv, keep), scale=cfg.softmax_scale())
    ref = jnp.einsum("bshk,hkd->bsd", ref, p["w_o"])
    # decode path: per-row prefix-aware cache from the reference constructor
    cache = init_kv_cache(cfg, B, S, dtype=jnp.float32, prefix_reserve=pres,
                          per_row=True)
    cache["k"] = cache["k"].at[0, pres - P:pres].set(pk[0])
    cache["v"] = cache["v"].at[0, pres - P:pres].set(pv[0])
    cache["lo"] = cache["lo"].at[0].set(pres - P)
    dec = []
    for s in range(S):
        y, cache = attention_decode_apply(p, x[:, s:s + 1], cfg, cache)
        dec.append(y[:, 0])
    dec = jnp.stack(dec, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_striped_cp_prefix_broadcast(key):
    """(b): striped-CP attention folds prefix rows into the carry (single-
    device fallback path) and matches the pairs-formulation reference."""
    from repro.models.attention import flash_attention_pairs
    from repro.models.cp_attention import striped_cp_attention

    B, S, H, Hkv, dh, P = 2, 64, 4, 2, 8, 5
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    pk = jax.random.normal(ks[3], (B, P, Hkv, dh), jnp.float32)
    pv = jax.random.normal(ks[4], (B, P, Hkv, dh), jnp.float32)
    keep = jnp.asarray([[1.0] * P, [0.0] * P])  # row 1 owns no prefix
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ref = flash_attention_pairs(q, k, v, block=32, causal=True,
                                positions=pos, kv_prefix=(pk, pv, keep),
                                scale=1 / np.sqrt(dh))
    out = striped_cp_attention(q, k, v, pos, None, None, block=32,
                               kv_prefix=(pk, pv, keep))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # sanity: the prefix-owning row actually differs from prefix-free attn
    bare = striped_cp_attention(q, k, v, pos, None, None, block=32)
    assert float(np.max(np.abs(np.asarray(out - bare)[0]))) > 1e-3
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(bare)[1],
                               rtol=2e-5, atol=2e-5)
    # shard_map path (1-device mesh): the replicated-prefix in_specs plumbing
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    out_sm = striped_cp_attention(q, k, v, pos, None, mesh, axis="model",
                                  block=32, kv_prefix=(pk, pv, keep))
    np.testing.assert_allclose(np.asarray(out_sm), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pool_bind_generate_matches_forward_greedy(key):
    """(c): the jitted pool data plane — bind (prefill + prefix fold) then
    on-device greedy generation — reproduces the train-path greedy
    continuation for a LoRA and a prefix tenant side by side."""
    from repro.launch.steps import (build_decode_bind_step,
                                    build_decode_micro_step,
                                    decode_prefix_reserve, init_decode_pool)

    cfg = CFG
    model = build_model(cfg)
    backbone = model.init(key)
    mta = MultiTaskAdapters(cfg, [AdapterConfig("lora", rank=4),
                                  AdapterConfig("prefix", rank=4)])
    params = mta.init(jax.random.PRNGKey(2))
    pres = decode_prefix_reserve(mta)
    rows, max_len, cap = 2, 16, 5
    pool = init_decode_pool(model, rows, max_len, cap, prefix_reserve=pres)
    bind = build_decode_bind_step(model, mta, max_len, pres)
    micro = build_decode_micro_step(model, mta, pres)
    slots = {k: jnp.asarray(v)
             for k, v in mta.decode_row_slots([0, 1]).items()}
    scales = {k: jnp.asarray(mta.scales(k)) for k in mta.kind_tasks}
    prompt = np.asarray([[4, 9, 2, 7]], np.int32)
    for r in (0, 1):
        s1 = {k: v[r:r + 1] for k, v in slots.items()}
        pool = bind(backbone, params, pool, jnp.asarray(r),
                    jnp.asarray(prompt), jnp.asarray(prompt.shape[1]), s1,
                    scales, jnp.asarray(cap))
    for _ in range(cap - 1):
        pool = micro(backbone, params, pool, slots, scales)
    acct = jax.device_get({"n_out": pool["n_out"], "active": pool["active"],
                           "out": pool["out"], "lo": pool["state"]["lo"]})
    assert list(acct["active"]) == [0, 0]
    assert list(acct["n_out"]) == [cap, cap]
    # prefix row's window opens over its folded rows; LoRA row's does not
    assert acct["lo"][1] == pres - 4 and acct["lo"][0] == pres
    for r in (0, 1):
        gen = np.asarray(acct["out"][r])
        seq = np.concatenate([prompt[0], gen[:-1]])
        ctxf = mta.ctx_factory(TaskSegments((r,), 2))
        out = model.forward(backbone, {"tokens": jnp.asarray(seq[None])},
                            adapters=params, ctx_factory=ctxf,
                            return_logits=True)
        greedy = np.asarray(out["logits"], np.float32)[0].argmax(-1)
        np.testing.assert_array_equal(
            gen, greedy[prompt.shape[1] - 1:],
            err_msg=f"row {r}: pool generation != train-path greedy")


def _coserve_service(**kw):
    kw.setdefault("lr", 5e-3)
    kw.setdefault("n_micro", 1)
    kw.setdefault("enable_fusion", False)
    kw.setdefault("reserve_slots", 4)
    kw.setdefault("seed", 0)
    kw.setdefault("coserve", CoServeConfig(decode_slots=2, decode_max_len=32,
                                           max_new_cap=8, slo_seconds=1.0))
    return MuxTuneService(CFG, ParallelismSpec(), **kw)


def test_service_coserve_training_loss_parity():
    """(d): interleaved decode traffic must not perturb training: per-task
    losses match the traffic-free run to rtol 2e-4, every request completes,
    and the SLO accounting (p50/p99, token counts) is populated."""
    steps = 5

    def run(with_traffic):
        svc = _coserve_service(auto_recalibrate=False)
        svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                             seed=0), target_steps=steps)
        svc.submit(make_task("b", "qa", 2, AdapterConfig("prefix", rank=4),
                             seed=1), target_steps=steps)
        if with_traffic:
            svc.submit_request("a", [3, 5, 7], max_new_tokens=5)
            svc.submit_request("b", [2, 4, 6, 8], max_new_tokens=4)
        losses, dec = [], 0
        for _ in range(steps):
            m = svc.step()
            losses.append(np.asarray(m.per_task_loss))
            dec += m.decode_tokens
        return svc, np.asarray(losses), dec

    ref_svc, ref_losses, ref_dec = run(False)
    svc, losses, dec = run(True)
    assert ref_dec == 0
    assert dec >= 9  # both requests fully decoded
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-4)
    acc = svc.accounting()["coserve"]
    assert acc["completed_requests"] == 2
    assert acc["decode_p50_s"] > 0.0 and acc["decode_p99_s"] >= acc["decode_p50_s"]
    for req in svc.coserve.requests.values():
        assert req.state == "done"
        assert len(req.tokens_out) == req.max_new_tokens
    # per-tenant decode-token billing (effective-token accounting)
    assert svc.record("a").decode_tokens == 5
    assert svc.record("b").decode_tokens == 4


def test_service_coserve_request_lifecycle_on_churn():
    """Requests of a departing tenant are cancelled; a request for a not-yet-
    resident tenant waits without blocking ready traffic behind it."""
    svc = _coserve_service(auto_recalibrate=False)
    svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=8)
    r_ghost = svc.submit_request("ghost", [1, 2], max_new_tokens=2)
    r_a = svc.submit_request("a", [3, 5], max_new_tokens=3)
    svc.step()
    assert r_a.state in ("decoding", "done")
    assert r_ghost.state == "pending"  # non-resident head did not block a
    svc.step()
    assert r_a.state == "done"
    r_b = svc.submit_request("a", [4, 4], max_new_tokens=100)
    assert r_b.state == "rejected" and r_b.reason == "length_caps"
    r_c = svc.submit_request("a", [9, 9], max_new_tokens=2)
    svc.cancel("a")
    assert r_c.state == "cancelled" and r_c.reason == "tenant_departed"
    # last tenant out drops the engine; a fresh tenant + request must serve
    # against the NEW engine's pool (scheduler detects the pool swap)
    svc.submit(make_task("c", "sst2", 2, AdapterConfig("lora", rank=4),
                         seed=3), target_steps=8)
    r_d = svc.submit_request("c", [5, 6], max_new_tokens=2)
    svc.step(); svc.step()
    assert r_d.state == "done" and len(r_d.tokens_out) == 2


def test_family_guard_rejects_coserve_requests():
    """Families without a full-depth KV stack can't prefill-into-cache:
    the request is rejected at submit instead of crashing the training
    iteration its bind would have interleaved into."""
    svc = MuxTuneService(smoke_config("zamba2-2.7b"), ParallelismSpec())
    r = svc.submit_request("x", [1, 2], max_new_tokens=2)
    assert r.state == "rejected" and r.reason == "family_unsupported"
    assert not svc.coserve.has_traffic()


def test_auto_recalibration_on_drift():
    """Satellite: the rolling-window refit fires inside ``step`` when the
    analytic profile's prediction drifts from measured wall times, and the
    refit profile lands in BOTH the planner and the admission gate."""
    svc = _coserve_service(auto_recalibrate=True, drift_threshold=0.5,
                           drift_window=3)
    svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=30)
    for _ in range(8):
        svc.step()
    # the analytic TPU profile is orders of magnitude off on CPU: the drift
    # guard must have refit at least once
    assert svc.recalibrations >= 1
    assert "__wall__" in svc.planner.hw.calibration
    assert svc.admission.hw is svc.planner.hw
    # post-refit predictions track measured wall times to within the knee
    # fit's tolerance (vs ~1e3+ analytic mismatch)
    pred = svc.predicted_iteration_seconds()
    meas = np.median([w for _, _, w in svc.calibration_trace[-3:]])
    assert 0.1 < pred / meas < 10.0


def test_sample_tokens_determinism_and_filters(key):
    """On-device sampling: fixed keys replay exactly; temperature 0 is exact
    argmax; top-k=1 and a tiny top-p nucleus both collapse to greedy."""
    from repro.launch.steps import sample_tokens

    B, V = 4, 64
    logits = jax.random.normal(key, (B, V), jnp.float32) * 3.0
    temp = jnp.asarray([0.0, 0.8, 1.2, 0.5], jnp.float32)
    top_k = jnp.asarray([0, 5, 0, 3], jnp.int32)
    top_p = jnp.asarray([1.0, 1.0, 0.9, 0.7], jnp.float32)
    rng = jnp.asarray([[0, i] for i in range(B)], jnp.uint32)
    t1, r1 = sample_tokens(logits, temp, top_k, top_p, rng)
    t2, r2 = sample_tokens(logits, temp, top_k, top_p, rng)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    assert int(t1[0]) == greedy[0]                  # temp 0 row: exact argmax
    assert np.any(np.asarray(r1) != np.asarray(rng))  # keys advanced
    hot = jnp.full((B,), 5.0, jnp.float32)
    tk, _ = sample_tokens(logits, hot, jnp.ones((B,), jnp.int32),
                          jnp.ones((B,), jnp.float32), rng)
    np.testing.assert_array_equal(np.asarray(tk), greedy)
    tp, _ = sample_tokens(logits, hot, jnp.zeros((B,), jnp.int32),
                          jnp.full((B,), 1e-6, jnp.float32), rng)
    np.testing.assert_array_equal(np.asarray(tp), greedy)


def test_batched_bind_matches_single_binds(key):
    """Tentpole: ONE batched multi-row chunked-prefill launch (padded
    prompts, per-row true lengths) produces the exact pool state and greedy
    trajectories of two legacy single-row binds."""
    from repro.launch.steps import (build_decode_batched_bind_step,
                                    build_decode_bind_step,
                                    build_decode_micro_step,
                                    decode_prefix_reserve, greedy_sampling,
                                    init_decode_pool)

    cfg = CFG
    model = build_model(cfg)
    backbone = model.init(key)
    mta = MultiTaskAdapters(cfg, [AdapterConfig("lora", rank=4),
                                  AdapterConfig("prefix", rank=4)])
    params = mta.init(jax.random.PRNGKey(2))
    pres = decode_prefix_reserve(mta)
    rows, max_len, cap = 2, 16, 4
    slots = {k: jnp.asarray(v)
             for k, v in mta.decode_row_slots([0, 1]).items()}
    scales = {k: jnp.asarray(mta.scales(k)) for k in mta.kind_tasks}
    # mixed true lengths inside one prompt bucket (row 0 is padded)
    prompts = np.asarray([[4, 9, 2, 0], [7, 1, 3, 5]], np.int32)
    lengths = np.asarray([3, 4], np.int32)
    bind_n = build_decode_batched_bind_step(model, mta, max_len, pres)
    pool_b = init_decode_pool(model, rows, max_len, cap, prefix_reserve=pres)
    pool_b = bind_n(backbone, params, pool_b, jnp.asarray([0, 1]),
                    jnp.asarray(prompts), jnp.asarray(lengths), slots, scales,
                    jnp.asarray([cap, cap]), greedy_sampling(2))
    bind1 = build_decode_bind_step(model, mta, max_len, pres)
    pool_s = init_decode_pool(model, rows, max_len, cap, prefix_reserve=pres)
    for r in (0, 1):
        s1 = {k: v[r:r + 1] for k, v in slots.items()}
        lp = int(lengths[r])
        pool_s = bind1(backbone, params, pool_s, jnp.asarray(r),
                       jnp.asarray(prompts[r:r + 1, :lp]), jnp.asarray(lp),
                       s1, scales, jnp.asarray(cap))
    micro = build_decode_micro_step(model, mta, pres)
    for _ in range(cap - 1):
        pool_b = micro(backbone, params, pool_b, slots, scales)
        pool_s = micro(backbone, params, pool_s, slots, scales)
    for k in ("out", "n_out", "active"):
        np.testing.assert_array_equal(np.asarray(pool_b[k]),
                                      np.asarray(pool_s[k]),
                                      err_msg=f"pool[{k}] batched != single")
    for k in ("pos", "lo"):
        np.testing.assert_array_equal(np.asarray(pool_b["state"][k]),
                                      np.asarray(pool_s["state"][k]),
                                      err_msg=f"state[{k}] batched != single")


def test_service_sampling_determinism_and_greedy_equivalence():
    """Same seed -> bit-identical sampled generation (across different pool
    rows); temperature 0 ignores the seed and equals the legacy greedy
    default."""
    svc = _coserve_service(auto_recalibrate=False)
    svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=10)
    sa = svc.submit_request("a", [3, 5, 7], max_new_tokens=5,
                            temperature=0.8, top_k=8, seed=11)
    sb = svc.submit_request("a", [3, 5, 7], max_new_tokens=5,
                            temperature=0.8, top_k=8, seed=11)
    ga = svc.submit_request("a", [2, 4, 6], max_new_tokens=4)  # legacy greedy
    gb = svc.submit_request("a", [2, 4, 6], max_new_tokens=4,
                            temperature=0.0, seed=123)
    for _ in range(8):
        svc.step()
        if all(r.state == "done" for r in (sa, sb, ga, gb)):
            break
    assert all(r.state == "done" for r in (sa, sb, ga, gb))
    assert list(sa.tokens_out) == list(sb.tokens_out)
    assert list(ga.tokens_out) == list(gb.tokens_out)


def test_continuous_batching_mid_iteration_bind_and_parity():
    """Acceptance: a request submitted MID-iteration (between training
    micro-steps) binds onto a free pool row and begins decoding within the
    same iteration — while the training losses stay exactly
    traffic-independent (rtol 2e-4 vs the traffic-free run)."""
    steps = 3

    def run(with_traffic):
        svc = _coserve_service(auto_recalibrate=False, n_micro=4)
        svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                             seed=0), target_steps=steps)
        mid = [None]
        if with_traffic:
            svc.submit_request("a", [3, 5, 7], max_new_tokens=4)
            orig = svc.coserve.interleave_fn
            calls = [0]

            def patched(engine):
                cb = orig(engine)

                def wrapped():
                    calls[0] += 1
                    if calls[0] == 2 and mid[0] is None:
                        mid[0] = svc.submit_request("a", [2, 4],
                                                    max_new_tokens=3)
                    cb()
                return wrapped
            svc.coserve.interleave_fn = patched
        losses = []
        for _ in range(steps):
            m = svc.step()
            losses.append(np.asarray(m.per_task_loss))
        return svc, mid[0], np.asarray(losses)

    _, _, ref_losses = run(False)
    svc, req, losses = run(True)
    assert req is not None
    # bound within the SAME iteration it was submitted in, via the
    # continuous-batching path — not parked until the next prepare()
    assert svc.coserve.mid_iteration_binds >= 1
    assert req.bind_clock == req.submit_clock
    assert req.state == "done" and len(req.tokens_out) == 3
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-4)


def test_decode_calibration_scale_fit():
    """Satellite: ``calibrate_profile(decode_samples=...)`` fits the
    ``"__decode__"`` scale so ``decode_token_latency`` reproduces measured
    per-micro-step decode seconds — independently of the training wall
    scale."""
    from repro.core.cost_model import (CostModel, HardwareProfile,
                                       calibrate_profile)

    par = ParallelismSpec()
    base = HardwareProfile()
    bare = CostModel(CFG, [], par, base)
    scale = 3.7
    samples = [(r, float(c), scale * bare.decode_token_latency(r, c))
               for r, c in [(1, 8), (2, 16), (2, 24), (1, 30)]]
    hw = calibrate_profile(CFG, par, [], base_hw=base, decode_samples=samples)
    np.testing.assert_allclose(hw.calibration["__decode__"], scale, rtol=1e-6)
    cm = CostModel(CFG, [], par, hw)
    for r, c, meas in samples:
        np.testing.assert_allclose(cm.decode_token_latency(r, int(c)), meas,
                                   rtol=1e-6)
    # the decode fit must not inherit a training wall scale: with both
    # channels present, each lands in its own key
    tr_hw = calibrate_profile(CFG, par, [], base_hw=base,
                              decode_samples=samples)
    tr_hw.calibrate("__wall__", 100.0)
    assert tr_hw.decode_scale() == pytest.approx(scale)


def test_service_decode_calibration_channel():
    """Service wiring: warm decode segments feed ``decode_trace``; a
    ``calibrate()`` installs ``"__decode__"`` into the live profile and the
    calibrated estimator tracks the measured micro-step seconds."""
    from repro.core.cost_model import CostModel

    svc = _coserve_service(auto_recalibrate=False)
    svc.submit(make_task("a", "sst2", 2, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=8)
    for i in range(5):
        # sustained traffic: the first iteration's segment is cold (micro-step
        # jit compile) and excluded; later warm segments feed the trace
        svc.submit_request("a", [3, 5, 7], max_new_tokens=6,
                           request_id=f"r{i}")
        svc.step()
    assert len(svc.decode_trace) >= 1
    hw = svc.calibrate()
    assert "__decode__" in hw.calibration
    assert svc.planner.hw is hw and svc.admission.hw is hw
    cm = CostModel(svc.cfg, [], svc.parallelism, hw)
    r, ctx, s = svc.decode_trace[-1]
    pred = cm.decode_token_latency(r, int(max(ctx, 1)))
    assert 0.1 < pred / s < 10.0


def test_slo_class_preemption():
    """A class-0 request arriving while the single decode row is held by a
    class-2 request evicts it: the victim re-queues (pool-generation
    recovery re-prefills it later), the urgent request binds, BOTH finish
    with full-length outputs, and the eviction is counted."""
    svc = _coserve_service(
        auto_recalibrate=False,
        coserve=CoServeConfig(decode_slots=1, max_tokens_per_iter=1))
    svc.submit(make_task("a", "sst2", 1, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=12)
    lo = svc.submit_request("a", np.arange(1, 6), max_new_tokens=6,
                            request_id="lo", slo_class=2)
    svc.step()
    assert lo.state == "decoding"  # holds the only row
    hi = svc.submit_request("a", np.arange(1, 4), max_new_tokens=2,
                            request_id="hi", slo_class=0)
    svc.step()
    assert svc.coserve.preemptions == 1
    assert hi.state in ("decoding", "done")
    for _ in range(16):
        if lo.state == hi.state == "done":
            break
        svc.step()
    assert lo.state == hi.state == "done"
    assert len(lo.tokens_out) == 6 and len(hi.tokens_out) == 2
    assert svc.coserve.accounting()["preemptions"] == 1


def test_preemption_disabled_preserves_fcfs_binding():
    """With preempt=False a later class-0 request waits for the row instead
    of evicting the class-2 holder."""
    svc = _coserve_service(
        auto_recalibrate=False,
        coserve=CoServeConfig(decode_slots=1, max_tokens_per_iter=1,
                              preempt=False))
    svc.submit(make_task("a", "sst2", 1, AdapterConfig("lora", rank=4),
                         seed=0), target_steps=12)
    lo = svc.submit_request("a", np.arange(1, 6), max_new_tokens=3,
                            request_id="lo", slo_class=2)
    svc.step()
    svc.submit_request("a", np.arange(1, 4), max_new_tokens=2,
                       request_id="hi", slo_class=0)
    svc.step()
    assert svc.coserve.preemptions == 0
    assert lo.state in ("decoding", "done")  # never evicted
