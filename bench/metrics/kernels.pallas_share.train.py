"""Share of the training steps' device time spent in Pallas kernels (%):
the leaf ``tpu_custom_call`` operations inside the ``jit_step`` programs
of the traced steps, over those programs' device time.  It stands in for
the kernels' roofline shares until the kernels carry names the trace can
tell apart (see PERF.md)."""
from tracered import is_pallas


def read(ctx):
    red = ctx["trace"]
    total, n = red.module_time(lambda m: m == "jit_step")
    if not n or total <= 0:
        return None
    kernel = red.op_time(lambda o: o["module"] == "jit_step" and is_pallas(o))
    return 100.0 * kernel / total
