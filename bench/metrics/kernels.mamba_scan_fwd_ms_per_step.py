"""Device time of the chunked-scan forward Pallas kernel
(``mamba_scan_fwd``, the forward and its recompute under remat) inside the
training steps, per traced step (ms)."""
from devtime import kernel_ms_per_step


def read(ctx):
    return kernel_ms_per_step(ctx["trace"], "mamba_scan_fwd")
