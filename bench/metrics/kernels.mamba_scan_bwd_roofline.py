"""Share of its roofline the chunked-scan backward kernels reach (%): the
least time the chip could take for the scan backward of every Mamba layer
at a step's tokens (the larger of its operations over peak bf16 FLOP/s and
its bytes over peak HBM bytes/s, counted by the family's ``scan_work``)
over the device time of ``mamba_scan_bwd_state`` and
``mamba_scan_bwd_chunk`` per traced step."""
from roofline_share import scan_roofline


def read(ctx):
    return scan_roofline(ctx, "bwd", "mamba_scan_bwd_state",
                         "mamba_scan_bwd_chunk")
