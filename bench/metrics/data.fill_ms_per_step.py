"""Host time the loader spends making the next micro-batch's tokens per
window step (ms): the summed ``engine.prefetch.fill`` spans, the part of
``engine.prefetch`` before the host-to-device copy.  None where the
program opens no such span."""
from readers import per_step_ms, window_spans

FILL = "engine.prefetch.fill"


def read(ctx):
    if not any(s[0] == FILL for s in window_spans(ctx)):
        return None
    return per_step_ms(ctx, FILL)
