"""Device time of the ``packed_attention_fwd`` Pallas kernel inside the
training steps, per traced step (ms)."""
from devtime import kernel_ms_per_step


def read(ctx):
    return kernel_ms_per_step(ctx["trace"], "packed_attention_fwd")
