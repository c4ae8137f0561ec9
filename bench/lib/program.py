"""The system under test, built from a configuration and a traffic file.

This is the one place the benchmark touches the program: it builds the
model (through the configuration family's adapter, ``bench/adapters/
<family>.py``), the optimizer and the trainer the way the repository's
training launcher does, and hands on the adapter's moves of weights
between the reference's layout and the program's.  Inputs and weights
come from the benchmark (``bench/inputs/``, ``bench/reference/``), never
from the program.
"""
from __future__ import annotations

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
    ``model(cfg, kcfg, compute_dtype)``, ``to_program``/``from_program``
    (weights from the reference's layout to the program's and back) and
    ``train_flops(cfg, data)``."""
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


def build(cfg: dict, traffic: dict, seed: int, *, trace: bool = False,
          bench: str = BENCH):
    """Model, optimizer and trainer, sharing one telemetry object; spans
    (with profiler annotations) only when ``trace``.  The model and the
    weight layout come from the family's adapter under ``bench``."""
    from repro import obs as obs_mod
    from repro import optimizers
    from repro.configs.base import ObsConfig, TrainConfig
    from repro.training.trainer import Trainer

    ocfg = ObsConfig(enabled=trace, trace_annotations=trace)
    obs = obs_mod.Obs(ocfg)
    opt_spec = traffic["optimizer"]
    kcfg = kfac_config(opt_spec, ocfg)
    fam = adapter(cfg, bench)
    model = fam.model(cfg, kcfg, cfg["precision"]["compute"])
    if opt_spec["name"] != "kfac":
        raise ValueError(f"unknown optimizer {opt_spec['name']!r}")
    opt = optimizers.kfac(model, kcfg, None,
                          family=opt_spec.get("family", "categorical"),
                          obs=obs)
    tcfg = TrainConfig(steps=0, seed=seed, log_every=1 << 30, obs=ocfg)
    trainer = Trainer(model, opt, tcfg, None, None, obs=obs)
    return SimpleNamespace(model=model, opt=opt, trainer=trainer, obs=obs,
                           to_program=fam.to_program,
                           from_program=fam.from_program)

