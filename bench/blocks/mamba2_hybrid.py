"""The Mamba-2 hybrid block (``granitemoehybrid`` with no experts, as
Granite-4.0-H): the configuration's ``layer_types`` repeat one period of
Mamba-2 and attention layers, and every layer is a mixer followed by its own
SwiGLU MLP, each under a pre-RMSNorm:

    h = h + r * mixer(rms(h));   h = h + r * mlp(rms(h))

with ``r`` the ``residual_multiplier``.  The embedding is multiplied by
``embedding_multiplier``, the logits are divided by ``logits_scaling``, and
the head is tied.

The Mamba-2 mixer, from the published equations (one group, no projection
bias): ``[z, x, B, C, dt] = h W_in``; a depthwise causal convolution of
``mamba_d_conv`` taps (with a bias where ``mamba_conv_bias``) over
``x || B || C``, then silu; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; per head the recurrence

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T,   y_t = H_t C_t + D x_t

then ``y * silu(z)``, an RMSNorm over ``d_in`` and ``W_out``.  The stack
evaluates the recurrence by chunks (``ssd``); ``recurrence`` is the same
equation one step at a time, and a test ties the two.  Attention is
grouped-query and causal, with rotary embeddings only where
``position_embedding_type`` is ``"rope"`` (Granite's is ``"nope"``) and the
softmax scale ``attention_multiplier``.

Adapter sites: ``ssm_in`` and ``ssm_out`` on the Mamba layers, the four
attention projections on the attention layers, the MLP's three matmuls on
every layer.  The benchmark stacks a site over the layers that carry it, in
layer order (``[L_site, cap, ...]``); the program stacks each kind's layers
``[period, index within the period, cap, ...]``, an MLP site under both
kinds.  Prefix tuning is not offered: four attention layers would carry it.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import weights as wt
import work

SSM_TARGETS = ("ssm_in", "ssm_out")
ATTN_TARGETS = ("attn_q", "attn_k", "attn_v", "attn_o")
MLP_TARGETS = ("mlp_gate", "mlp_up", "mlp_down")
KINDS = ("mamba", "attention")
#: the program's adapter and parameter group of each layer kind
GROUP = {"mamba": "mamba", "attention": "attn"}
#: query rows a block of the reference's attention computes at a time
ATTN_BLOCK = 512


# ---------------------------------------------------------------------------
# sizes and sites


def period(cfg: Dict) -> List[str]:
    """The shortest period of ``layer_types`` that repeats to all of it."""
    lt = list(cfg["layer_types"])
    for p in range(1, len(lt) + 1):
        if len(lt) % p == 0 and lt == lt[:p] * (len(lt) // p):
            return lt[:p]
    raise AssertionError("unreachable")


def dims(cfg: Dict) -> Dict[str, int]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    lt = list(cfg["layer_types"])
    nh, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "Lm": lt.count("mamba"), "La": lt.count("attention"),
            "nh": nh, "P": hd, "N": cfg["mamba_d_state"], "d_in": nh * hd,
            "W": cfg["mamba_d_conv"], "Q": cfg["mamba_chunk_size"]}


def targets(cfg: Dict, group: str) -> tuple:
    """``"mixers"``: each layer's mixer projections (``ssm_in``/``ssm_out``
    on the Mamba layers, q/k/v/o on the attention layers); ``"all"``: those
    and every layer's MLP."""
    return {"mixers": SSM_TARGETS + ATTN_TARGETS,
            "all": SSM_TARGETS + ATTN_TARGETS + MLP_TARGETS}[group]


def site_dims(cfg: Dict) -> Dict[str, tuple]:
    n = dims(cfg)
    d, q, kv = n["d"], n["H"] * n["dh"], n["Hkv"] * n["dh"]
    return {"ssm_in": (d, 2 * n["d_in"] + 2 * n["N"] + n["nh"]),
            "ssm_out": (n["d_in"], d),
            "attn_q": (d, q), "attn_k": (d, kv), "attn_v": (d, kv),
            "attn_o": (q, d),
            "mlp_gate": (d, n["F"]), "mlp_up": (d, n["F"]), "mlp_down": (n["F"], d)}


def site_kinds(site: str) -> tuple:
    """The layer kinds that carry a site."""
    if site in SSM_TARGETS:
        return ("mamba",)
    if site in ATTN_TARGETS:
        return ("attention",)
    return KINDS


def site_layers(cfg: Dict, site: str) -> List[int]:
    """The layers that carry a site, in layer order."""
    kinds = site_kinds(site)
    return [i for i, k in enumerate(cfg["layer_types"]) if k in kinds]


# ---------------------------------------------------------------------------
# the program's configuration


def program_config(cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's sizes, layer pattern and multipliers so that the
    program runs what the file says."""
    from repro.configs import get_config

    prog = cfg["program"]
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in prog.get("overrides", {}).items()}
    ac = get_config(prog["arch"]).with_overrides(**over)
    if ac.family != "hybrid" or ac.shared_attention:
        raise RuntimeError(f"program arch {prog['arch']!r} is of family "
                           f"{ac.family!r} (shared attention "
                           f"{ac.shared_attention}), not the file's Mamba-2 "
                           "hybrid block")
    unsupported = {"mamba_n_groups": 1, "mamba_proj_bias": False,
                   "attention_bias": False, "num_local_experts": 0,
                   "hidden_act": "silu", "normalization_function": "rmsnorm"}
    for k, v in unsupported.items():
        if cfg.get(k, v) != v:
            raise RuntimeError(f"{k} = {cfg[k]!r}: the block takes {v!r}")
    from repro.configs import CONV_W

    n = dims(cfg)
    if n["W"] != CONV_W:
        raise RuntimeError(f"mamba_d_conv {n['W']}: the program's conv has "
                           f"{CONV_W} taps")
    want = {
        "num_layers": n["L"], "d_model": n["d"], "num_heads": n["H"],
        "num_kv_heads": n["Hkv"], "head_dim": n["dh"], "d_ff": n["F"],
        "vocab_size": n["V"], "tie_embeddings": cfg["tie_word_embeddings"],
        "layer_pattern": list(cfg["layer_types"]),
        "ssm_heads": n["nh"], "ssm_head_dim": n["P"], "ssm_state": n["N"],
        "ssm_chunk": n["Q"], "ssm_conv_bias": cfg["mamba_conv_bias"],
        "positional": cfg["position_embedding_type"],
        "attention_scale": float(cfg["attention_multiplier"]),
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
        "norm_eps": float(cfg["rms_norm_eps"]), "gated_mlp": True,
    }
    got = {k: getattr(ac, k) for k in want if hasattr(ac, k)}
    got["head_dim"] = ac.resolved_head_dim()
    got["layer_pattern"] = list(ac.layer_pattern) * ac.n_periods
    got["ssm_heads"] = ac.ssm_expand * ac.d_model // ac.ssm_head_dim
    if got != want:
        raise RuntimeError(f"program config {got} does not run the file's {want}")
    return ac


# ---------------------------------------------------------------------------
# weights


def backbone_shapes(cfg: Dict) -> Dict[str, Dict[str, tuple]]:
    """Each per-layer leaf's shape by layer kind, without the layer axes
    (``backbone`` stacks a kind's layers ``[period, index within it, ...]``,
    the program's own layout, so that both read one copy)."""
    n = dims(cfg)
    d, F, C = n["d"], n["F"], n["d_in"] + 2 * n["N"]
    mlp = {"ln2": (d,), "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}
    mamba = {"ln": (d,), "w_in": (d, site_dims(cfg)["ssm_in"][1]),
             "conv": (n["W"], C), "dt_bias": (n["nh"],), "a_log": (n["nh"],),
             "d_skip": (n["nh"],), "norm": (n["d_in"],),
             "w_out": (n["d_in"], d), **mlp}
    if cfg["mamba_conv_bias"]:
        mamba["conv_b"] = (C,)
    attn = {"ln1": (d,), "wq": (d, n["H"], n["dh"]), "wk": (d, n["Hkv"], n["dh"]),
            "wv": (d, n["Hkv"], n["dh"]), "wo": (n["H"], n["dh"], d), **mlp}
    return {"mamba": mamba, "attention": attn}


def _per_head(seed: int, name: str, L: int, nh: int, lo: float, hi: float):
    """[L, nh] log-uniform draws in [lo, hi], float32."""
    u = jax.random.uniform(wt._key(seed, name), (L, nh), jnp.float32)
    return jnp.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))


def backbone(cfg: Dict, seed: int) -> Dict:
    """The frozen backbone's leaves from the seed, bfloat16 (traced inside
    ``weights.make_backbone``'s jitted call).  Matrices are N(0, 0.02),
    norms 1 + N(0, 0.1); the conv's taps and bias are uniform in
    +-1/sqrt(taps), as a depthwise ``Conv1d`` starts; the Mamba-2 heads
    start as the published initialisation draws them:
    ``A`` log-uniform in [1, 16], ``dt`` log-uniform in [1e-3, 0.1] through
    the inverse softplus into ``dt_bias``, ``D`` one.  Each kind's layers
    are drawn in layer order and stacked ``[period, index within it, ...]``
    (the one copy on the chip: the program's tree takes the same arrays)."""
    n = dims(cfg)
    out = {"tok": (0.02 * jax.random.normal(wt._key(seed, "tok"),
                                            (n["V"], n["d"]))).astype(wt.BF16),
           "final_norm": (1.0 + 0.1 * jax.random.normal(
               wt._key(seed, "final_norm"), (n["d"],))).astype(wt.BF16)}
    for kind, shapes in backbone_shapes(cfg).items():
        L = n["Lm"] if kind == "mamba" else n["La"]
        leaves = {}
        for name, shp in shapes.items():
            key = f"{kind}/{name}"
            if name == "a_log":
                v = jnp.log(_per_head(seed, key, L, n["nh"], 1.0, 16.0))
            elif name == "dt_bias":
                dt = _per_head(seed, key, L, n["nh"], 1e-3, 0.1)
                v = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
            elif name == "d_skip":
                v = jnp.ones((L,) + shp, jnp.float32)
            elif name in ("conv", "conv_b"):
                bound = 1.0 / np.sqrt(n["W"])
                v = jax.random.uniform(wt._key(seed, key), (L,) + shp,
                                       jnp.float32, -bound, bound)
            else:
                norm = name in ("ln", "ln1", "ln2", "norm")
                v = wt._layers(wt._key(seed, key), L, shp, 0.1 if norm else 0.02,
                               1.0 if norm else 0.0)
            leaves[name] = _by_period(cfg, v.astype(wt.BF16), kind)
        out[kind] = leaves
    return out


def _by_period(cfg: Dict, a, kind: str):
    """[L_kind, ...] in layer order -> [period, index within it, ...]."""
    k = period(cfg).count(kind)
    return a.reshape((a.shape[0] // k, k) + a.shape[1:])


def program_backbone(cfg: Dict, bb: Dict) -> Dict:
    """The backbone in the program's parameter tree layout: the same
    arrays, nested as the program nests them."""
    m, a = bb["mamba"], bb["attention"]
    mlp_leaves = ("w_gate", "w_up", "w_down")
    return {
        "embed": {"tok": bb["tok"]}, "final_norm": {"w": bb["final_norm"]},
        "blocks": {
            "mamba": {"ln": {"w": m["ln"]},
                      "mamba": {k: v for k, v in m.items()
                                if k not in ("ln", "ln2") + mlp_leaves},
                      "ln2": {"w": m["ln2"]},
                      "mlp": {k: m[k] for k in mlp_leaves}},
            "attn": {"ln1": {"w": a["ln1"]},
                     "attn": {"w_q": a["wq"], "w_k": a["wk"], "w_v": a["wv"],
                              "w_o": a["wo"]},
                     "ln2": {"w": a["ln2"]},
                     "mlp": {k: a[k] for k in mlp_leaves}}}}


# ---------------------------------------------------------------------------
# adapters


def adapter_shapes(cfg: Dict, t, prefix_rows: int) -> Dict[str, Dict]:
    """A tenant's leaves per site at its own rank, ``[L_site, ...]`` over
    the layers that carry the site."""
    if t.method == "prefix":
        raise NotImplementedError("the Mamba-2 hybrid block offers no prefix "
                                  "tuning")
    sd = site_dims(cfg)
    out = {}
    for site in t.targets:
        L = len(site_layers(cfg, site))
        din, dout = sd[site]
        if t.method == "lora":
            out[site] = {"a": (L, din, t.rank), "b": (L, t.rank, dout)}
        else:  # bottleneck adapter on the site's output
            out[site] = {"down": (L, dout, t.rank), "up": (L, t.rank, dout)}
    return out


def _layer_order(cfg: Dict, site: str):
    """Where each layer of a site sits among the kinds' stacks
    concatenated in ``site_kinds`` order, in layer order."""
    lt = cfg["layer_types"]
    rank = {}
    pos = 0
    for kind in site_kinds(site):
        for i, k in enumerate(lt):
            if k == kind:
                rank[i] = pos
                pos += 1
    return np.asarray([rank[i] for i in site_layers(cfg, site)])


def from_program(cfg: Dict, tree):
    """The program's adapter tree as per-site stacks ``[L_site, cap, ...]``
    in layer order: each kind's ``[period, index, cap, ...]`` flattened, an
    MLP site's two kinds interleaved back into layer order."""
    parts: Dict = {}
    for kind in KINDS:
        for method, sites in tree.get(GROUP[kind], {}).items():
            for site, leaves in sites.items():
                for leaf, a in leaves.items():
                    flat = a.reshape((-1,) + a.shape[2:])
                    parts.setdefault(method, {}).setdefault(site, {}).setdefault(
                        leaf, {})[kind] = flat
    out: Dict = {}
    for method, sites in parts.items():
        out[method] = {}
        for site, leaves in sites.items():
            order = _layer_order(cfg, site)
            out[method][site] = {
                leaf: jnp.concatenate([by_kind[k] for k in site_kinds(site)])[order]
                for leaf, by_kind in leaves.items()}
    return out


def to_program(cfg: Dict, stacks):
    """Per-site stacks as the program's adapter tree."""
    out: Dict = {GROUP[k]: {} for k in KINDS}
    for method, sites in stacks.items():
        for site, leaves in sites.items():
            layers = site_layers(cfg, site)
            for kind in site_kinds(site):
                rows = np.asarray([j for j, i in enumerate(layers)
                                   if cfg["layer_types"][i] == kind])
                dst = out[GROUP[kind]].setdefault(method, {}).setdefault(site, {})
                for leaf, a in leaves.items():
                    dst[leaf] = _by_period(cfg, a[rows], kind)
    return {g: v for g, v in out.items() if v}


# ---------------------------------------------------------------------------
# the reference's block


def _conv(x, w, b):
    """Depthwise causal convolution over time: x [N, S, C], w [W, C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(jnp.float32) for i in range(W))
    return y if b is None else y + b.astype(jnp.float32)


def recurrence(ref, x, dt, A, B, C):
    """The Mamba-2 recurrence, one time step after another under
    ``lax.scan``: ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``,
    ``y_t = H_t C_t``.  x [N, S, nh, P], dt [N, S, nh], A [nh], B/C
    [N, S, St], float32; returns y [N, S, nh, P].  The definition that
    ``ssd`` is tested against (``bench/tests``)."""
    N, S, nh, P = x.shape

    def step(H, xs):
        xt, dtt, bt, ct = xs
        H = (jnp.exp(dtt * A)[..., None, None] * H
             + ref._mm("nhp,ns->nhps", xt * dtt[..., None], bt))
        return H, ref._mm("nhps,ns->nhp", H, ct)

    H0 = jnp.zeros((N, nh, P, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, H0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def ssd(ref, x, dt, A, B, C, chunk: int):
    """The same recurrence evaluated by chunks of ``chunk`` steps (the
    state-space dual form), as ``recurrence`` takes it: within a chunk
    ``y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j`` plus the
    entering state's ``exp(cum_i) H C_i``; the state leaves a chunk as
    ``exp(cum_Q) H + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T``, with
    ``cum`` the running sum of ``dt A`` inside the chunk.  ``lax.scan`` over
    the chunks, each rematerialised; ``C B^T`` once per chunk (one group)."""
    N, S, nh, P = x.shape
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    @jax.checkpoint
    def one(H, xs):
        xc, dtc, bc, cc = xs                          # [N, Q, ...]
        cum = jnp.cumsum(dtc * A, axis=1)             # [N, Q, nh]
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # [N, i, j, nh]
        mask = causal[None, :, :, None]
        decay = jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)
        cb = ref._mm("nis,njs->nij", cc, bc)
        w = cb[..., None] * decay * dtc[:, None, :, :]  # [N, i, j, nh]
        y = ref._mm("nijh,njhp->nihp", w, xc)
        y = y + jnp.exp(cum)[..., None] * ref._mm("nis,nhps->nihp", cc, H)
        last = cum[:, -1]                             # [N, nh]
        wx = xc * (jnp.exp(last[:, None] - cum) * dtc)[..., None]
        H = (jnp.exp(last)[..., None, None] * H
             + ref._mm("njhp,njs->nhps", wx, bc))
        return H, y

    def chunks(a):  # [N, S, ...] -> [S / Q, N, Q, ...]
        a = a.reshape((N, S // Q, Q) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    H0 = jnp.zeros((N, nh, P, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(one, H0, tuple(chunks(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(N, S, nh, P)


def mixer(ref, lw, h, ad, method, scale):
    """The Mamba-2 mixer over ``h [N, S, d]`` (after the layer's norm)."""
    n = ref.n
    N, S, _ = h.shape
    d_in, St, nh, P = n["d_in"], n["N"], n["nh"], n["P"]
    proj = ref._site(ad, "ssm_in", h, ref._mm("nsd,de->nse", h, lw["w_in"]),
                     method, scale)
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * St], axis=-1)
    bias = lw.get("conv_b") if ref.cfg["mamba_conv_bias"] else None
    xbc = jax.nn.silu(_conv(xbc, lw["conv"], bias))
    x, B, C = jnp.split(xbc, [d_in, d_in + St], axis=-1)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lw["a_log"].astype(jnp.float32))
    xh = x.reshape(N, S, nh, P)
    y = ssd(ref, xh, dt, A, B, C, n["Q"])
    y = y + lw["d_skip"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(N, S, d_in) * jax.nn.silu(z)
    y = ref._rms(y, lw["norm"])
    return ref._site(ad, "ssm_out", y, ref._mm("nse,ed->nsd", y, lw["w_out"]),
                     method, scale)


def attention(ref, lw, h, ad, pos, kmask, method, scale):
    """Causal grouped-query attention over ``h [N, S, d]``, computed
    ``ATTN_BLOCK`` query rows at a time."""
    n = ref.n
    N, S, _ = h.shape
    H, Hkv, dh = n["H"], n["Hkv"], n["dh"]
    G = H // Hkv
    q = ref._site(ad, "attn_q", h, ref._mm("nsd,dhk->nshk", h, lw["wq"]), method, scale)
    k = ref._site(ad, "attn_k", h, ref._mm("nsd,dhk->nshk", h, lw["wk"]), method, scale)
    v = ref._site(ad, "attn_v", h, ref._mm("nsd,dhk->nshk", h, lw["wv"]), method, scale)
    if ref.cfg["position_embedding_type"] == "rope":
        q, k = ref._rope(q, pos), ref._rope(k, pos)
    mult = ref.cfg.get("attention_multiplier")
    c = float(mult) if mult else 1.0 / np.sqrt(dh)
    blk = min(ATTN_BLOCK, S)
    qb = q.reshape(N, S // blk, blk, Hkv, G, dh)

    @jax.checkpoint
    def rows(i, qi):
        s = ref._mm("nskgd,ntkd->nkgst", qi, k) * c
        qpos = i * blk + jnp.arange(blk)
        allow = (jnp.arange(S)[None, :] <= qpos[:, None])[None] & kmask[:, None, :]
        s = jnp.where(allow[:, None, None], s, -jnp.inf)
        return ref._mm("nkgst,ntkd->nskgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(lambda a: rows(*a), (jnp.arange(S // blk), jnp.moveaxis(qb, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(N, S, H * dh)
    y = ref._mm("nsq,qd->nsd", o, lw["wo"].reshape(H * dh, -1))
    return ref._site(ad, "attn_o", o, y, method, scale)


def mlp(ref, lw, h, ad, method, scale):
    g = ref._site(ad, "mlp_gate", h, ref._mm("nsd,df->nsf", h, lw["w_gate"]),
                  method, scale)
    u = ref._site(ad, "mlp_up", h, ref._mm("nsd,df->nsf", h, lw["w_up"]),
                  method, scale)
    m = jax.nn.silu(g) * u
    return ref._site(ad, "mlp_down", m, ref._mm("nsf,fd->nsd", m, lw["w_down"]),
                     method, scale)


def layer(ref, kind, x, lw, ad, pos, kmask, method, scale):
    """One layer: its mixer, then its MLP, each a residual branch under
    ``residual_multiplier``."""
    r = float(ref.cfg["residual_multiplier"])
    if kind == "mamba":
        y = mixer(ref, lw, ref._rms(x, lw["ln"]), ad, method, scale)
    else:
        y = attention(ref, lw, ref._rms(x, lw["ln1"]), ad, pos, kmask, method, scale)
    x = x + r * y
    return x + r * mlp(ref, lw, ref._rms(x, lw["ln2"]), ad, method, scale)


def _period_stacks(ref, ad):
    """Per-site adapter stacks ``[L_site, ...]`` split by period and by the
    kind of layer they sit on: {kind: {site: {leaf: [period, k, ...]}}}."""
    cfg = ref.cfg
    out = {kind: {} for kind in KINDS}
    lt = cfg["layer_types"]
    for site, leaves in ad.items():
        layers = site_layers(cfg, site)
        for kind in site_kinds(site):
            rows = np.asarray([j for j, i in enumerate(layers) if lt[i] == kind])
            out[kind][site] = {leaf: _by_period(cfg, a[rows], kind)
                               for leaf, a in leaves.items()}
    return out


def stack(ref, bb, x, ad, pos, kmask, method, scale):
    """Logits of the layers: ``lax.scan`` over the periods, each period
    rematerialised and each layer inside it again (the backward holds a
    period's layer inputs and one layer's internals: at 2 x 4096 tokens the
    float32 inputs of all 40 layers alone take 2.5 GB); the embedding and
    logits multipliers, the final norm and the tied head."""
    cfg = ref.cfg
    x = x * float(cfg["embedding_multiplier"])
    weights = {k: bb[k] for k in KINDS}
    adapters = _period_stacks(ref, ad)
    fns = {k: jax.checkpoint(lambda x, lw, a, k=k: layer(
        ref, k, x, lw, a, pos, kmask, method, scale)) for k in KINDS}

    @jax.checkpoint
    def one_period(x, w, a):
        seen = {k: 0 for k in KINDS}
        for kind in period(cfg):
            i = seen[kind]
            seen[kind] += 1
            lw = {n: v[i] for n, v in w[kind].items()}
            la = {s: {n: v[i] for n, v in leaves.items()}
                  for s, leaves in a[kind].items()}
            x = fns[kind](x, lw, la)
        return x

    x, _ = jax.lax.scan(lambda x, xs: (one_period(x, *xs), None), x,
                        (weights, adapters))
    x = ref._rms(x, bb["final_norm"])
    return ref._mm("nsd,vd->nsv", x, bb["tok"]) / float(cfg["logits_scaling"])


# ---------------------------------------------------------------------------
# work


def matmul_params(cfg: Dict) -> int:
    """Weights every token is multiplied by: each layer's mixer projections
    and MLP, and the tied LM head."""
    n = dims(cfg)
    sd = site_dims(cfg)
    per = {kind: sum(i * o for s, (i, o) in sd.items() if kind in site_kinds(s))
           for kind in KINDS}
    return n["Lm"] * per["mamba"] + n["La"] * per["attention"] + n["d"] * n["V"]


def scan_work(cfg: Dict, tokens: int) -> Dict[str, float]:
    """Operations and bytes the Mamba-2 scan of every Mamba layer requires
    for ``tokens`` tokens, forward and backward, counted as the SSD
    algorithm does the work at the published shapes and chunk, whatever
    implements it:

    * forward, per chunk of Q: ``C B^T`` over the chunk's causal pairs,
      Q (Q + 1) / 2 of them as ``work.attention_pairs`` counts a sequence's
      (2 St a pair), once per group (one group); per head the decayed
      intra-chunk product with ``x`` over the same pairs (2 P a pair), the
      chunk's state ``B^T x`` (2 Q St P) and the carried state's output
      ``C H`` (2 Q St P);
    * backward: twice the forward (``bench/work.py``'s convention);
    * bytes: each operand read once and each result written once, at the
      dtype the model holds it in: forward reads x, B, C (bfloat16) and the
      per-head log-decay and input gain (float32) and writes y (bfloat16);
      backward reads those and dy and writes dx, dB, dC (bfloat16) and the
      two gate gradients (float32).  States carried between chunks stay on
      the chip and are not counted.

    Recomputation (the forward a rematerialised layer runs again inside the
    backward) is not counted, so the shares these counts give can never
    read above 100%."""
    n = dims(cfg)
    Q, St, nh, P, d_in = n["Q"], n["N"], n["nh"], n["P"], n["d_in"]
    per_token = (Q + 1) * St + nh * ((Q + 1) * P + 4 * St * P)
    fwd = float(n["Lm"] * tokens * per_token)
    io = d_in * 2 + 2 * St * 2 + 2 * nh * 4          # x, B, C, two gates
    fwd_bytes = float(n["Lm"] * tokens * (io + d_in * 2))
    bwd_bytes = float(n["Lm"] * tokens * (io + d_in * 2 + io))
    return {"fwd_flops": fwd, "fwd_bytes": fwd_bytes,
            "bwd_flops": 2.0 * fwd, "bwd_bytes": bwd_bytes}


def adapter_flops_fwd(cfg: Dict, method: str, rank: int, targets) -> float:
    """Forward FLOPs per token of one tenant's adapter over the layers that
    carry its sites."""
    sd = site_dims(cfg)
    per = 0.0
    for s in targets:
        din, dout = sd[s]
        one = 2.0 * rank * (din + dout) if method == "lora" else 4.0 * rank * dout
        per += len(site_layers(cfg, s)) * one
    return per


def train_flops(cfg: Dict, tenants) -> float:
    """Required FLOPs of one training iteration: every tenant's frozen
    lengths (one micro-batch each), forward plus backward: the frozen
    matmuls, causal attention in the attention layers, the SSD scan of the
    Mamba layers (``scan_work``) and each tenant's adapter."""
    n = dims(cfg)
    attn_pair = 4.0 * n["dh"] * n["H"] * n["La"]
    total = 0.0
    for t in tenants:
        toks = sum(t.lengths)
        sw = scan_work(cfg, toks)
        total += 4.0 * matmul_params(cfg) * toks
        total += 3.0 * attn_pair * work.attention_pairs(t.lengths)
        total += sw["fwd_flops"] + sw["bwd_flops"]
        total += 3.0 * adapter_flops_fwd(cfg, t.method, t.rank, t.targets) * toks
    return total
