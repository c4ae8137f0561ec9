"""Tiny cells for the CPU tests: each real cell's files, cut to a size a
test run holds (the widths shrink here only; the chip runs the files as
they are)."""
from types import SimpleNamespace

from bench.lib import harness as H

TINY_LM = dict(hidden_size=96, intermediate_size=192, num_attention_heads=3,
               num_key_value_heads=1, head_dim=32, num_hidden_layers=2,
               vocab_size=512)


def _lm_train(c):
    c["config"].update(TINY_LM)
    c["traffic"]["data"].update(batch=2, seq=256, n_batches=4)
    c["traffic"]["optimizer"]["kernel_backend"] = "xla"


def _ae_train(c):
    c["config"].update(encoder=[64, 32, 16, 8])
    c["traffic"]["data"].update(examples=1024, batch=256, latent=4)


CUTS = {"smollm-kfac-train": _lm_train, "ae-kfac-train": _ae_train}


def cell(name: str, warm_steps: int = 1) -> dict:
    c = H.cell(name, H.manifest())
    CUTS[name](c)
    c["traffic"]["warm_steps"] = warm_steps
    return c


def args(seed: int, seconds: float = 0.3, trace: int = 0):
    return SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


def cpu():
    import jax
    return jax.devices("cpu")[0], H.peaks_for("TPU v5 lite")
