"""Device time of the training step's forward pass, per traced step (ms):
the leaf operations of ``jit_step`` whose op_name in the compiled step is
under ``train.forward`` and not under ``transpose(`` (``bench/devtime.py``)."""
from devtime import phase_ms_per_step


def read(ctx):
    return phase_ms_per_step(ctx, "forward")
