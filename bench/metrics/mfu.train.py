"""The whole training step's share of the chip's peak: the forward and
backward FLOPs the model needs per step (``bench/lib/flops.py``; no
recomputation, no optimizer work) × steps in the traced window, over the
window's host-clock length × chips × peak FLOP/s."""
from bench.lib import flops


def read(ctx):
    cfg, data = ctx.config, ctx.traffic["data"]
    if cfg["family"] == "llama":
        per_step = flops.llama_train_flops(cfg, data["batch"], data["seq"])
    elif cfg["family"] == "mlp_autoencoder":
        enc = list(cfg["encoder"])
        per_step = flops.mlp_train_flops(enc + enc[-2::-1], data["batch"])
    else:
        return None
    if not ctx.steps or ctx.wall <= 0:
        return None
    return (100.0 * per_step * ctx.steps
            / (ctx.wall * ctx.n_devices * ctx.peaks["flops_per_s"]))
