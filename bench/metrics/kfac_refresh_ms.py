"""Milliseconds per step, amortised, inside the optimizer's
``kfac/scheduled_inverse_refresh`` stage (warm-up and periodic inverse
refreshes and the γ sweep): the program's own span summed over the traced
window and divided by the steps in it."""
from bench.lib import trace


def read(ctx):
    spans = [(n, s, e) for n, s, e in trace.clip(ctx.trace["host"], ctx.lo,
                                                  ctx.hi)
             if n == "kfac/scheduled_inverse_refresh"]
    if not spans or not ctx.steps:
        return None
    return sum(e - s for _, s, e in spans) * 1e-6 / ctx.steps
