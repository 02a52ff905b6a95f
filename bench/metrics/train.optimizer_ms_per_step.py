"""Device time of the training step's optimizer, per traced step (ms): the
leaf operations of ``jit_step`` under ``train.optimizer`` (AdamW, the
finite-loss guard and the adapters' update; ``bench/devtime.py``)."""
from devtime import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "optimizer")
