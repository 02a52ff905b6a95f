"""Share of its roofline the chunked-scan forward kernel reaches (%): the
least time the chip could take for the scan forward of every Mamba layer
at a step's tokens (the larger of its operations over peak bf16 FLOP/s and
its bytes over peak HBM bytes/s, counted by the family's ``scan_work``)
over the kernel's device time per traced step.  The forward a
rematerialised layer runs again inside the backward is in the time and not
in the work."""
from roofline_share import scan_roofline


def read(ctx):
    return scan_roofline(ctx, "fwd", "mamba_scan_fwd")
