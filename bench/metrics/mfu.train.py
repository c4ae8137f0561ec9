"""The whole training step's share of the chip's peak: the forward and
backward FLOPs the model needs per step (the configuration family's
adapter, ``train_flops``, over ``bench/lib/flops.py``; no recomputation,
no optimizer work) × steps in the traced window, over the window's
host-clock length × chips × peak FLOP/s."""
from bench.lib import program


def read(ctx):
    per_step = program.adapter(ctx.config, ctx.bench).train_flops(
        ctx.config, ctx.traffic["data"])
    if per_step is None or not ctx.steps or ctx.wall <= 0:
        return None
    return (100.0 * per_step * ctx.steps
            / (ctx.wall * ctx.n_devices * ctx.peaks["flops_per_s"]))
