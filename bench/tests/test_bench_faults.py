"""A run with the timed path broken underneath comes out not correct.

The look for a chip is skipped (``run_cell`` is what ``main`` calls after
it); everything else is a whole run of a tiny cut of each cell on the
CPU, with the cell's own limits.  The faults a training cell can have
(``bench/lib/faults.py``): a step that returns its state unchanged, and
half of the batch left out with the mean taken over the rest; and, of the
schedule the cells compare, the Levenberg–Marquardt rule switched off (λ
never adapted).  A sound run of the same cut comes out correct.  The
cells on a mesh run the same in ``bench/tests/test_bench_mesh.py``.
"""
import pytest

from bench import run as R
from bench.lib import faults, program
from bench.tests import tiny

CELLS = list(tiny.CUTS)
FAULTS = ("half_batch", "state_unchanged", "lambda_rule_off")


def run(name, seed=2_147_483_659):
    device, peaks = tiny.cpu()
    return R.run_cell(tiny.cell(name), tiny.args(seed), device, peaks)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(monkeypatch, name, fault):
    monkeypatch.setattr(program, "build",
                        faults.broken_build(fault, program.build))
    res = run(name)
    assert not res["correct"], res["checks"]
