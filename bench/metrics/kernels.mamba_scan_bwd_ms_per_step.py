"""Device time of the chunked-scan backward Pallas kernels
(``mamba_scan_bwd_state`` and ``mamba_scan_bwd_chunk``) inside the
training steps, per traced step (ms)."""
from devtime import kernel_ms_per_step


def read(ctx):
    return kernel_ms_per_step(ctx["trace"], "mamba_scan_bwd_state",
                              "mamba_scan_bwd_chunk")
