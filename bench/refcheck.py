"""The reference's side of the check: it regenerates each tenant's training
sequences from the seed and trains them with the plain reference for the
steps the program's readings cover.  ``lower=True`` computes the control,
the reference in the next precision down, put in the program's place."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import traffic as tr
from check import training_numbers
from reference import Reference, adamw_step, sequences_batch


def tenant_batches(tenant: tr.Tenant, layout, vocab: int, seed: int,
                   steps: int) -> List[Dict]:
    """The tenant's micro-batch of each step, drawn from its token stream
    in the order the program draws it."""
    stream = tr.token_stream(tenant.tenant_id, vocab, seed)
    out = []
    for _ in range(steps):
        seqs = [{"tokens": [next(stream) for _ in range(length)],
                 "last_label_zero": length < padded}
                for length, padded in layout]
        out.append(sequences_batch(seqs, pad_to=tenant.pad_len))
    return out


def _tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


def train_tenant(loss_grad, adam, ad0: Dict, bb, batches: List[Dict]) -> Dict:
    """Losses, the gradient of every step and the change after them, with
    the tenant's compiled loss-and-gradient and AdamW programs."""
    params = jax.tree.map(jnp.asarray, ad0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grads = [], []
    for k, batch in enumerate(batches):
        loss, g = loss_grad(params, bb, batch)
        losses.append(float(loss))
        grads.append(jax.device_get(g))
        params, m, v = adam(params, g, m, v, jnp.float32(k + 1))
    return {"losses": losses, "grads": grads,
            "change": _tree_sub(jax.device_get(params), ad0)}


def compile_tenants(ref: Reference, bb, tenants, adapters0, batches,
                    hp: Dict[str, float], workers: int = 8) -> List[tuple]:
    """Each tenant's (loss-and-gradient, AdamW) programs, compiled ahead on
    a pool of threads (the compiler works in parallel; a run in a checkout
    that has compiled them before finds them in the persistent cache)."""
    from concurrent.futures import ThreadPoolExecutor

    def one(t, ad0, batch):
        lg = jax.jit(jax.value_and_grad(functools.partial(
            ref.tenant_loss, method=t.method, scale=t.scale)))
        ad = jax.tree.map(jnp.asarray, ad0)
        adam = jax.jit(functools.partial(adamw_step, lr=t.lr, **hp))
        return (lg.lower(ad, bb, batch).compile(),
                adam.lower(ad, ad, ad, ad, jnp.float32(1)).compile())

    with ThreadPoolExecutor(workers) as ex:
        futs = [ex.submit(one, t, a, b[0])
                for t, a, b in zip(tenants, adapters0, batches)]
        return [f.result() for f in futs]


def _leaf_norms(tree, prefix: str) -> Dict[str, float]:
    out = {}
    for site, leaves in tree.items():
        for leaf, a in leaves.items():
            out[f"{prefix}/{site}/{leaf}"] = float(np.linalg.norm(np.asarray(a, np.float64)))
    return out


def training_reference(cell, tenants, bb, adapters0, layout, seed: int,
                       steps: int, hp: Dict[str, float],
                       lower: bool = False) -> Dict:
    ref = Reference(cell.cfg, lower=lower)
    vocab = cell.cfg["vocab_size"]
    out = {"losses": [], "grads": [], "change": [],
           "grad_norms": [dict() for _ in range(steps)]}
    batches = [tenant_batches(t, layout[t.tenant_id], vocab, seed, steps)
               for t in tenants]
    progs = compile_tenants(ref, bb, tenants, adapters0, batches, hp)
    for i, (ad0, b, (lg, adam)) in enumerate(zip(adapters0, batches, progs)):
        r = train_tenant(lg, adam, ad0, bb, b)
        out["losses"].append(r["losses"])
        out["grads"].append(r["grads"][0])
        out["change"].append(r["change"])
        for k, g in enumerate(r["grads"]):
            out["grad_norms"][k].update(_leaf_norms(g, str(i)))
    return out


def program_training(run, steps: int) -> Dict:
    """The program's readings in the comparison's layout."""
    rd = run.readings
    return {"losses": [ls[:steps] for ls in rd["losses"]],
            "grads": rd["grads"],
            "change": [_tree_sub(p, jax.device_get(a0))
                       for p, a0 in zip(rd["params"], run.adapters0)]}


def train_check(run, steps: int, control: bool = False) -> Dict[str, Dict]:
    """``training_numbers`` of the program against the reference, under
    ``"program"``; with ``control`` also those of the control (the
    reference one precision down, in the program's place), under
    ``"control"``."""
    args = (run.cell, run.tenants, run.backbone, run.adapters0,
            run.readings["layout"], run.seed, steps, run.adam)
    ref = training_reference(*args)
    out = {"program": training_numbers(program_training(run, steps), ref)}
    if control:
        out["control"] = training_numbers(training_reference(*args, lower=True),
                                          ref)
    return out
