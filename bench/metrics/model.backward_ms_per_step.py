"""Device time of the training step's backward pass, per traced step (ms):
the leaf operations of ``jit_step`` under ``transpose(``, the forward that
rematerialised layers recompute there included (``bench/devtime.py``)."""
from devtime import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "backward")
