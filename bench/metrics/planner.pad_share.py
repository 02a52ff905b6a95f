"""Share of the computed training tokens that are padding, over the
window's iterations (%): 1 - effective / computed, from ``StepMetrics``.
A count of the plan; it repeats exactly."""


def read(ctx):
    steps = [m for m in ctx["window"].steps if m is not None]
    tok = sum(m.tokens for m in steps)
    return 100.0 * (1.0 - sum(m.effective_tokens for m in steps) / tok) if tok else None
