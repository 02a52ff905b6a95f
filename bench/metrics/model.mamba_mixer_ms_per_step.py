"""Device time of the Mamba-2 mixers inside the training steps, per traced
step (ms): the leaf operations of ``jit_step`` whose op_name in the
compiled step is under ``mixer.mamba`` (each Mamba layer's in-projection
through out-projection: conv, scan, gate, norm), in the forward, the
forward a rematerialised layer recomputes, and the backward
(``bench/devtime.py``)."""
from devtime import STEP, _per_step_ms, program_scopes, scopes

SCOPE = "mixer.mamba"


def read(ctx):
    red = ctx["trace"]
    sc = scopes(red.ops, program_scopes(ctx))
    return _per_step_ms(red, sum(o["dur"] for o, s in zip(red.ops, sc)
                                 if o["module"] == STEP and o.get("leaf", True)
                                 and SCOPE in s))
