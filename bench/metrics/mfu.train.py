"""Training work's share of the chip's peak over the window's untraced
steps (%): the FLOPs those iterations require (forward and
activation-gradient backward of the frozen backbone at every tenant's true
lengths, causal attention inside each sequence, each tenant's adapter at
its own rank; no recomputation, no padding) over their seconds on the
host clock (each step ends in the engine's device sync) times peak bf16
FLOP/s.  The steps under the profiler run slower and are left out."""
from work import train_flops


def read(ctx):
    win = ctx["window"]
    traced = set(ctx["traced_steps"])
    times = [te - ts for i, (ts, te) in enumerate(win.step_times)
             if i not in traced and win.steps[i] is not None]
    if not times:
        return None
    flops = len(times) * train_flops(ctx["cell"].cfg, ctx["run"].tenants)
    return 100.0 * flops / (sum(times) * ctx["peaks"].bf16_flops)
