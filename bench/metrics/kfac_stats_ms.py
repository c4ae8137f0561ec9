"""Milliseconds per step inside the optimizer's ``kfac/estimate_stats``
stage (gradients plus factor statistics): the program's own span, which
blocks on the stage's outputs at its close, summed over the traced window
and divided by the steps in it."""
from bench.lib import trace


def read(ctx):
    spans = [(n, s, e) for n, s, e in trace.clip(ctx.trace["host"], ctx.lo,
                                                  ctx.hi)
             if n == "kfac/estimate_stats"]
    if not spans or not ctx.steps:
        return None
    return sum(e - s for _, s, e in spans) * 1e-6 / ctx.steps
