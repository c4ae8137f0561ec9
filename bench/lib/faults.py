"""Faults planted in a built program, to show that the comparison which
decides ``correct`` catches them (``bench/tests/test_bench_faults.py`` at
the tiny cuts, ``bench/calibrate.py`` at a cell's own size):

* ``state_unchanged``: each step returns the parameters and the optimizer
  state it was given;
* ``half_batch``: each step sees the first half of its batch's rows, the
  mean taken over them;
* ``lambda_rule_off`` (in the optimizer's settings): λ never adapted;
* ``no_reduction`` (a cell on a mesh): each device computes the gradients
  and factor statistics of its own rows only, with no sum across devices:
  the statistics pass runs per device under ``shard_map`` and its outputs
  are declared replicated, so every later program takes each device's own
  copy as the whole.
"""
from __future__ import annotations

import copy
import dataclasses


def _wrap_update(prog, wrap):
    tr = prog.trainer
    tr.opt = dataclasses.replace(tr.opt, update=wrap(tr.opt.update))


def state_unchanged(prog, cfg, traffic):
    def wrap(update):
        def step(grads, state, params, batch, rng):
            _, _, metrics = update(grads, state, params, batch, rng)
            return params, state, metrics
        return step
    _wrap_update(prog, wrap)


def half_batch(prog, cfg, traffic):
    def wrap(update):
        def step(grads, state, params, batch, rng):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return update(grads, state, params, half, rng)
        return step
    _wrap_update(prog, wrap)


def no_reduction(prog, cfg, traffic):
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.optimizers.kfac import KFACEngine
    from bench.lib import program
    if prog.mesh is None:
        raise ValueError("no_reduction needs a cell on a mesh")
    pipe = prog.opt.update.__self__              # the K-FAC pipeline
    eng = pipe.engine
    # a meshless twin traces the per-device pass: inside shard_map the
    # mesh's sharding constraints have nothing to act on
    twin = KFACEngine(program.adapter(cfg).model(
        cfg, eng.cfg, cfg["precision"]["compute"]), eng.cfg, None,
        eng.family)
    local = jax.shard_map(twin.stats_grads, mesh=prog.mesh,
                          in_specs=(P(), P(), P("data"), P()),
                          out_specs=P(), check_vma=False)
    pipe._stats = jax.jit(local)


PLANTED = {"state_unchanged": state_unchanged, "half_batch": half_batch,
           "no_reduction": no_reduction}
# faults planted in the optimizer's settings, the program's alone
SETTINGS = {"lambda_rule_off": {"t1": 0}}


def plant(fault: str, prog, cfg, traffic):
    PLANTED[fault](prog, cfg, traffic)
    return prog


def broken_build(fault: str, build):
    """``build`` (``program.build``) with ``fault`` planted in what it
    builds, or set in the optimizer's settings."""
    def broken(cfg, traffic, *a, **k):
        if fault in SETTINGS:
            traffic = copy.deepcopy(traffic)
            traffic["optimizer"].update(SETTINGS[fault])
            return build(cfg, traffic, *a, **k)
        return plant(fault, build(cfg, traffic, *a, **k), cfg, traffic)
    return broken
