"""Host time to enqueue the training micro-steps per window step (ms):
the summed ``engine.micro_step`` spans."""
from readers import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "engine.micro_step")
