"""Weights made from ``--seed``: the frozen backbone and each tenant's
initial adapter, in the benchmark's own layout, and their installation into
the program under test.  The configuration's block family
(``bench/blocks/<family>.py``) names the leaves, their shapes and the
program's trees; what is here is the same for every family.

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

import blocks
from traffic import Tenant

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


def make_backbone(cfg: Dict, seed: int) -> Dict:
    """The frozen backbone from the seed, bfloat16, in one jitted call."""
    fam = blocks.family(cfg)
    return jax.jit(lambda: fam.backbone(cfg, seed))()


def adapter_shapes(cfg: Dict, t: Tenant, prefix_rows: int) -> Dict[str, Dict]:
    """A tenant's leaves per site at its own rank, ``[L, ...]`` with the
    site's layers first.  A prefix tenant has ``prefix_rows`` rows, the
    width of its stack."""
    return blocks.family(cfg).adapter_shapes(cfg, t, prefix_rows)


ZERO_INIT = ("b", "up")


def make_adapters(cfg: Dict, tenants: List[Tenant], seed: int,
                  prefix_rows: int) -> List[Dict]:
    """Each tenant's initial adapter: {site: {leaf: [L, ...] float32}} with
    bfloat16-representable values (the program stores adapters in bf16)."""
    def build():
        out = []
        for t in tenants:
            tree = {}
            for site, leaves in adapter_shapes(cfg, t, prefix_rows).items():
                tree[site] = {}
                for leaf, shp in leaves.items():
                    if leaf in ZERO_INIT:
                        v = jnp.zeros(shp, jnp.float32)
                    else:
                        k = _key(seed, f"{t.tenant_id}/{site}/{leaf}")
                        v = (0.02 * jax.random.normal(k, shp)
                             ).astype(BF16).astype(jnp.float32)
                    if t.method == "prefix" and t.rank < prefix_rows:
                        v = v.at[:, t.rank:].set(0.0)  # rows past its own length
                    tree[site][leaf] = v
            out.append(tree)
        return out

    return jax.jit(build)()


# ---------------------------------------------------------------------------
# installation into the program (its parameter layout is checked, not assumed)


def program_backbone(cfg: Dict, bb: Dict) -> Dict:
    """The backbone in the program's parameter tree layout."""
    return blocks.family(cfg).program_backbone(cfg, bb)


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


def install_adapters(cfg: Dict, service, tenants: List[Tenant],
                     adapters: List[Dict]) -> None:
    """Replace the program's freshly initialised adapter stacks with the
    seed's adapters, each at its tenant's slot, zero-padded to the stack's
    width, in one jitted call; untouched slots and sites stay zero (no row
    routes to them).  The family's maps carry the program's tree to the
    per-site stacks ``[L, cap, ...]`` and back."""
    fam = blocks.family(cfg)
    reg = service.engine.reg
    slots = tenant_slots(service, tenants)
    kinds = [t.method for t in tenants]
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        reg.adapter_params)

    def build(adapters):
        stacks = fam.from_program(
            cfg, jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), like))
        for kind, slot, tree in zip(kinds, slots, adapters):
            for site, leaves in tree.items():
                for leaf, v in leaves.items():
                    dst = stacks[kind][site][leaf]          # [L, cap, ...]
                    idx = (slice(None), slot) + tuple(slice(0, s) for s in v.shape[1:])
                    stacks[kind][site][leaf] = dst.at[idx].set(v.astype(dst.dtype))
        return fam.to_program(cfg, stacks)

    new = jax.jit(build)(adapters)
    same_layout(new, reg.adapter_params, "adapters")
    reg.adapter_params = new
