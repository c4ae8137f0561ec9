"""Milliseconds per step that the host spends at the training loop's
device-to-host sync sites: the program's spans ``kfac/read_step`` (the
step counter), ``train/finite_check`` (the non-finite guard),
``train/metrics_to_host`` (one scalar read per metric) and
``kfac/lambda_guard`` (the λ step's finite check), none of which blocks on
its own.  The spans are clipped to the traced window, summed and divided
by the steps in it.  Where the window holds none of them (a program
without these spans) the reader returns nothing."""
from bench.lib import trace

SITES = ("kfac/read_step", "train/finite_check", "train/metrics_to_host",
         "kfac/lambda_guard")


def read(ctx):
    spans = [(n, s, e) for n, s, e in trace.clip(ctx.trace["host"], ctx.lo,
                                                  ctx.hi)
             if n in SITES]
    if not spans or not ctx.steps:
        return None
    return sum(e - s for _, s, e in spans) * 1e-6 / ctx.steps
