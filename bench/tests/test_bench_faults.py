"""A run with the timed path broken underneath comes out not correct.

The look for a chip is skipped (``run_cell`` is what ``main`` calls after
it); everything else is a whole run of a tiny cut of each cell on the
CPU, with the cell's own limits.  The faults a training cell can have: a
step that returns its state unchanged, and half of the batch left out
with the mean taken over the rest; and, of the schedule the cells
compare, the Levenberg–Marquardt rule switched off (λ never adapted).  A
sound run of the same cut comes out correct.
"""
import copy
import dataclasses

import pytest

from bench import run as R
from bench.lib import program
from bench.tests import tiny

CELLS = list(tiny.CUTS)


def _unchanged(update):
    def step(grads, state, params, batch, rng):
        _, _, metrics = update(grads, state, params, batch, rng)
        return params, state, metrics
    return step


def _half_batch(update):
    def step(grads, state, params, batch, rng):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return update(grads, state, params, half, rng)
    return step


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch}
# faults planted in the optimizer's settings, the program's alone
SETTINGS = {"lambda_rule_off": {"t1": 0}}


def _run(monkeypatch, name, fault=None, seed=2_147_483_659):
    if fault is not None:
        build = program.build

        def broken(cfg, traffic, *a, **k):
            if fault in SETTINGS:
                traffic = copy.deepcopy(traffic)
                traffic["optimizer"].update(SETTINGS[fault])
                return build(cfg, traffic, *a, **k)
            prog = build(cfg, traffic, *a, **k)
            tr = prog.trainer
            tr.opt = dataclasses.replace(tr.opt,
                                         update=FAULTS[fault](tr.opt.update))
            return prog
        monkeypatch.setattr(program, "build", broken)
    device, peaks = tiny.cpu()
    return R.run_cell(tiny.cell(name), tiny.args(seed), device, peaks)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, name):
    res = _run(monkeypatch, name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS) + sorted(SETTINGS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(monkeypatch, name, fault):
    res = _run(monkeypatch, name, fault)
    assert not res["correct"], res["checks"]

