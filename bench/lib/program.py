"""The system under test, built from a configuration and a traffic file.

This is the one place the benchmark touches the program: it builds the
model (through the configuration family's adapter, ``bench/adapters/
<family>.py``), the optimizer and the trainer the way the repository's
training launcher does, and hands on the adapter's moves of weights
between the reference's layout and the program's.  Inputs and weights
come from the benchmark (``bench/inputs/``, ``bench/reference/``), never
from the program.

A traffic file may name a device mesh, ``"mesh": {"data": 4, "model":
1}``: the program is then built on it over the first devices JAX lists,
as the launcher builds it under ``--mesh local``, and the weights handed
to it are put on the mesh with the model's own parameter shardings.
Without ``mesh`` nothing is placed and the program is built meshless.
"""
from __future__ import annotations

import math
import os
import sys
from types import SimpleNamespace

from bench.lib import harness as H
from bench.lib.harness import BENCH, ROOT

SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def adapter(cfg: dict, bench: str = BENCH):
    """``bench/adapters/<family>.py`` of the configuration's family: its
    ``model(cfg, kcfg, compute_dtype, mesh=None)``, ``to_program``/
    ``from_program`` (weights from the reference's layout to the
    program's and back) and ``train_flops(cfg, data)``."""
    family = cfg["family"]
    try:
        return H.load_module("adapters", family, bench)
    except FileNotFoundError as e:
        raise FileNotFoundError(
            f"no adapter for the configuration family {family!r}: add "
            f"bench/adapters/{family}.py ({e})") from None


def kfac_config(opt: dict, obs_cfg):
    """KFACConfig from the traffic's optimizer entry: the keys it names,
    the program's defaults for the rest."""
    from repro.configs.base import KFACConfig
    kw = {k: v for k, v in opt.items() if k not in ("name", "family")}
    return KFACConfig(obs=obs_cfg, **kw)


def mesh(traffic: dict):
    """The traffic's ``mesh`` over the first devices JAX lists, or None."""
    if "mesh" not in traffic:
        return None
    import jax
    from repro.launch.mesh import make_mesh
    shape = traffic["mesh"]
    devices = jax.devices()[:math.prod(shape.values())]
    return make_mesh(tuple(shape.values()), tuple(shape), devices=devices)


def build(cfg: dict, traffic: dict, seed: int, *, trace: bool = False,
          bench: str = BENCH):
    """Model, optimizer and trainer, sharing one telemetry object; spans
    (with profiler annotations) only when ``trace``.  The model and the
    weight layout come from the family's adapter under ``bench``; on a
    mesh, ``to_program`` also puts the weights on it."""
    import jax
    from repro import obs as obs_mod
    from repro import optimizers
    from repro.configs.base import ObsConfig, TrainConfig
    from repro.training.trainer import Trainer

    ocfg = ObsConfig(enabled=trace, trace_annotations=trace)
    obs = obs_mod.Obs(ocfg)
    opt_spec = traffic["optimizer"]
    kcfg = kfac_config(opt_spec, ocfg)
    fam = adapter(cfg, bench)
    m = mesh(traffic)
    model = fam.model(cfg, kcfg, cfg["precision"]["compute"], mesh=m)
    if opt_spec["name"] != "kfac":
        raise ValueError(f"unknown optimizer {opt_spec['name']!r}")
    opt = optimizers.kfac(model, kcfg, m,
                          family=opt_spec.get("family", "categorical"),
                          obs=obs)
    tcfg = TrainConfig(steps=0, seed=seed, log_every=1 << 30, obs=ocfg)
    trainer = Trainer(model, opt, tcfg, m, None, obs=obs)
    to_program = fam.to_program
    if m is not None:
        to_program = lambda p: jax.device_put(fam.to_program(p),
                                              model.param_shardings())
    return SimpleNamespace(model=model, opt=opt, trainer=trainer, obs=obs,
                           mesh=m, to_program=to_program,
                           from_program=fam.from_program)
