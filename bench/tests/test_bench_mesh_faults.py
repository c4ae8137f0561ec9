"""A run of a cell on a mesh with the timed path broken underneath comes
out not correct: the tiny cut of each cell on four chips (and of the one
``tiny.probe_root`` adds to a copy of the benchmark), run whole on
four CPU devices in a child process (``bench/tests/mesh_cell.py
faults``), once with each fault such a cell can have
(``bench/lib/faults.py``): a step that returns its state unchanged, half
of the batch left out, and the exchange between chips left out (each
device's gradients and factor statistics of its own rows only)."""
import pytest

from bench.tests import mesh_cell, tiny


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    return tiny.on_four_devices(
        "faults", tiny.probe_root(tmp_path_factory.mktemp("mesh")))


@pytest.mark.parametrize("fault", mesh_cell.FAULTS)
@pytest.mark.parametrize("name", list(tiny.MESH_CUTS) + [tiny.PROBE])
def test_broken_run_on_four_devices_is_not_correct(four, name, fault):
    res = four[name][fault]
    assert not res["correct"], res["checks"]
