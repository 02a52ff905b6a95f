"""Host time the engine waits for the next micro-batch per window step
(ms): the summed ``engine.prefetch`` spans, which hold the loader's token
fill and the host-to-device copy."""
from readers import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "engine.prefetch")
