"""The ``mlp_autoencoder`` family: the program's ``MLP`` over the encoder's
widths and their mirror.  The reference's weights are the program's."""
from bench.lib import flops


def _dims(cfg):
    enc = list(cfg["encoder"])
    return enc + enc[-2::-1]


def model(cfg, kcfg, compute_dtype, mesh=None):
    from repro.models.mlp import MLP
    return MLP(_dims(cfg), nonlin=cfg["nonlin"], loss=cfg["loss"],
               mesh=mesh)


def to_program(p):
    return p


from_program = to_program


def train_flops(cfg, data):
    return flops.mlp_train_flops(_dims(cfg), data["batch"])
