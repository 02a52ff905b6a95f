"""Self time of the ``service.step`` span per window step (ms): its
duration minus what its child spans (engine iteration, decode binds,
micro-steps and syncs, re-plans) cover: the service layer's own host work."""
from readers import window_spans
from tracered import self_times


def read(ctx):
    st = self_times(window_spans(ctx), "service.step")
    return sum(st) / len(st) / 1e3 if st else None
