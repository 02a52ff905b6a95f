"""Weights made from ``--seed``: the frozen backbone and each tenant's
initial adapter, in the benchmark's own layout, and their installation into
the program under test.

The backbone is made on the device in one jitted call, in bfloat16, the type
it is served in.  Stacked per-layer leaves are drawn one layer at a time
inside that call, so the float32 draw of a wide layer never holds more than
one layer's matrix.  The reference reads the same arrays, which the program
never writes (its backbone is frozen and not donated), so both sides start
from identical values and the reference takes nothing that the program made.

Adapters start as each method's paper prescribes: LoRA ``a`` random and
``b`` zero, the bottleneck adapter's ``down`` random and ``up`` zero, prefix
keys and values random.  A tenant's leaves have its own rank; installation
pads them with zeros to the width of the program's per-method stack.  Zero
columns of ``a`` and ``b`` (and of ``down``/``up``) get zero gradients and
stay zero under AdamW without weight decay, so each tenant trains exactly
the rank and the targets it asked for.  A prefix tenant in a wider prefix
stack keeps every row of the stack (the program's attention sees them all):
its rows past its own length start at zero, and the reference trains the
same rows.
"""
from __future__ import annotations

import zlib
from typing import Dict, List

import jax
import jax.numpy as jnp

from traffic import Tenant
from work import dims, site_dims

BF16 = jnp.bfloat16


def _key(seed: int, name: str) -> jax.Array:
    """A key per (seed, leaf name): independent of how many leaves exist."""
    base = jax.random.PRNGKey(seed % (2**32))
    return jax.random.fold_in(base, zlib.crc32(name.encode()) % (2**31))


def _layers(key, L: int, shape: tuple, scale: float, offset: float = 0.0):
    """[L, *shape] bfloat16, drawn one layer at a time."""
    keys = jax.random.split(key, L)
    return jax.lax.map(
        lambda k: (offset + scale * jax.random.normal(k, shape, jnp.float32)
                   ).astype(BF16), keys)


def backbone_shapes(cfg: Dict) -> Dict[str, tuple]:
    n = dims(cfg)
    d, H, Hkv, dh, F = n["d"], n["H"], n["Hkv"], n["dh"], n["F"]
    s = {"ln1": (d,), "wq": (d, H, dh), "wk": (d, Hkv, dh), "wv": (d, Hkv, dh),
         "wo": (H, dh, d), "ln2": (d,)}
    if cfg["block"]["attention_bias"]:
        s.update({"bq": (H, dh), "bk": (Hkv, dh), "bv": (Hkv, dh), "bo": (d,)})
    if cfg["block"]["mlp"] == "swiglu":
        s.update({"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)})
    else:
        s.update({"w_fc1": (d, F), "w_fc2": (F, d)})
    return s


def make_backbone(cfg: Dict, seed: int) -> Dict:
    """The frozen backbone from the seed, bfloat16, in one jitted call."""
    n = dims(cfg)

    def build():
        out = {"tok": (0.02 * jax.random.normal(_key(seed, "tok"),
                                                (n["V"], n["d"]))).astype(BF16),
               "final_norm": (1.0 + 0.1 * jax.random.normal(
                   _key(seed, "final_norm"), (n["d"],))).astype(BF16),
               "layers": {}}
        if not cfg["tie_word_embeddings"]:
            out["unembed"] = (0.02 * jax.random.normal(
                _key(seed, "unembed"), (n["d"], n["V"]))).astype(BF16)
        for name, shp in backbone_shapes(cfg).items():
            norm = name in ("ln1", "ln2")
            out["layers"][name] = _layers(
                _key(seed, name), n["L"], shp, 0.1 if norm else 0.02,
                1.0 if norm else 0.0)
        return out

    return jax.jit(build)()


def adapter_shapes(cfg: Dict, t: Tenant, prefix_rows: int) -> Dict[str, Dict]:
    """A tenant's leaves per site, at its own rank (without the layer axis).
    A prefix tenant has ``prefix_rows`` rows, the width of its stack."""
    n = dims(cfg)
    if t.method == "prefix":
        kv = n["Hkv"] * n["dh"]
        return {"attn_prefix": {"pk": (prefix_rows, kv), "pv": (prefix_rows, kv)}}
    sd = site_dims(cfg)
    out = {}
    for site in t.targets:
        din, dout = sd[site]
        if t.method == "lora":
            out[site] = {"a": (din, t.rank), "b": (t.rank, dout)}
        else:  # bottleneck adapter on the site's output
            out[site] = {"down": (dout, t.rank), "up": (t.rank, dout)}
    return out


ZERO_INIT = ("b", "up")


def make_adapters(cfg: Dict, tenants: List[Tenant], seed: int,
                  prefix_rows: int) -> List[Dict]:
    """Each tenant's initial adapter: {site: {leaf: [L, ...] float32}} with
    bfloat16-representable values (the program stores adapters in bf16)."""
    L = dims(cfg)["L"]

    def build():
        out = []
        for t in tenants:
            tree = {}
            for site, leaves in adapter_shapes(cfg, t, prefix_rows).items():
                tree[site] = {}
                for leaf, shp in leaves.items():
                    if leaf in ZERO_INIT:
                        v = jnp.zeros((L,) + shp, jnp.float32)
                    else:
                        k = _key(seed, f"{t.tenant_id}/{site}/{leaf}")
                        v = (0.02 * jax.random.normal(k, (L,) + shp)
                             ).astype(BF16).astype(jnp.float32)
                    if t.method == "prefix" and t.rank < prefix_rows:
                        v = v.at[:, t.rank:].set(0.0)  # rows past its own length
                    tree[site][leaf] = v
            out.append(tree)
        return out

    return jax.jit(build)()


# ---------------------------------------------------------------------------
# installation into the program (its parameter layout is checked, not assumed)


def program_backbone(bb: Dict) -> Dict:
    """The backbone in the program's parameter tree layout."""
    lay = bb["layers"]
    attn = {"w_q": lay["wq"], "w_k": lay["wk"], "w_v": lay["wv"], "w_o": lay["wo"]}
    if "bq" in lay:
        attn.update({"b_q": lay["bq"], "b_k": lay["bk"], "b_v": lay["bv"],
                     "b_o": lay["bo"]})
    mlp = {k: lay[k] for k in ("w_gate", "w_up", "w_down", "w_fc1", "w_fc2")
           if k in lay}
    embed = {"tok": bb["tok"]}
    if "unembed" in bb:
        embed["unembed"] = bb["unembed"]
    return {"embed": embed, "final_norm": {"w": bb["final_norm"]},
            "layers": {"ln1": {"w": lay["ln1"]}, "attn": attn,
                       "ln2": {"w": lay["ln2"]}, "mlp": mlp}}


def same_layout(got, want, what: str) -> None:
    g = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), got)
    w = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), want)
    if g != w:
        raise RuntimeError(f"{what}: the program's parameter layout is not the "
                           f"one this benchmark installs:\n{w}\nvs\n{g}")


def tenant_slots(service, tenants: List[Tenant]) -> List[int]:
    """Each tenant's slot in its method's stack."""
    reg = service.engine.reg
    ids = [t.task_id for t in reg.tasks]
    return [int(reg.mta.task_slot[ids.index(t.tenant_id)]) for t in tenants]


def install_adapters(service, tenants: List[Tenant], adapters: List[Dict]) -> None:
    """Replace the program's freshly initialised adapter stacks with the
    seed's adapters, each at its tenant's slot, zero-padded to the stack's
    width, in one jitted call; untouched slots and sites stay zero (no row
    routes to them)."""
    reg = service.engine.reg
    slots = tenant_slots(service, tenants)
    kinds = [t.method for t in tenants]
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        reg.adapter_params)

    def build(adapters):
        stacks = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), like)
        for kind, slot, tree in zip(kinds, slots, adapters):
            for site, leaves in tree.items():
                for leaf, v in leaves.items():
                    dst = stacks[kind][site][leaf]          # [L, cap, ...]
                    idx = (slice(None), slot) + tuple(slice(0, s) for s in v.shape[1:])
                    stacks[kind][site][leaf] = dst.at[idx].set(v.astype(dst.dtype))
        return stacks

    new = jax.jit(build)(adapters)
    same_layout(new, reg.adapter_params, "adapters")
    reg.adapter_params = new
