"""Faults planted in the program under test, to read what the check makes
of a broken timed path.  Used by the tests and by measurement runs that
set the upper readings of the limits; never by a benchmark run.

* ``unchanged``: the training step returns its state unchanged (losses are
  still computed and reported);
* ``half_batch``: half of every tenant's sequences leave the loss: the mean
  is taken over the rest.

The exchange between chips and a token altered where it is produced are
faults of cells this benchmark does not have (one chip, training only).
"""
from __future__ import annotations

FAULTS = ("unchanged", "half_batch")


def plant(name: str):
    """Plant fault ``name``; returns a callable that takes it out again."""
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    return {"unchanged": _unchanged, "half_batch": _half_batch}[name]()


def _unchanged():
    from repro.core.engine import PEFTEngine

    make = PEFTEngine._make_step

    def make_step(self, htask_idx):
        step = make(self, htask_idx)

        def frozen(backbone, adapters, opt_state, slot_steps, batch, member_ids, acc):
            import jax

            keep = jax.tree.map(lambda a: a.copy(), (adapters, opt_state, slot_steps))
            _, _, _, acc = step(backbone, adapters, opt_state, slot_steps, batch,
                                member_ids, acc)
            return keep + (acc,)

        return frozen

    PEFTEngine._make_step = make_step
    return lambda: setattr(PEFTEngine, "_make_step", make)


def _half_batch():
    import numpy as np

    from repro.data.loader import HTaskLoader

    nxt = HTaskLoader.__next__

    def half(self):
        batch = nxt(self)
        mask = batch["loss_mask"].copy()
        seen = {}
        for b, row in enumerate(self.plan.rows):
            for s in row.segments:
                k = seen.get(row.task, 0)
                seen[row.task] = k + 1
                if k % 2:
                    mask[b, s.start:s.start + s.padded] = 0.0
        batch["loss_mask"] = mask.astype(np.float32)
        return batch

    HTaskLoader.__next__ = half
    return lambda: setattr(HTaskLoader, "__next__", nxt)
