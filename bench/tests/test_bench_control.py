"""The control comes out not correct: the plain reference put in the
program's place, its model computing in bfloat16 on bfloat16 parameters
(the precision one step below the configurations' float32; its factors,
inverses and update stay float32), against the reference in float32,
under each cell's own limits — at a tiny cut of the cell on the CPU.
On the chip, at the cells' own sizes, ``bench/calibrate.py`` reads the
same comparison on a dozen seeds."""
import jax
import pytest

from bench.runners import train as T
from bench.lib import data as data_mod
from bench.lib import harness as H
from bench.tests import tiny


@pytest.mark.parametrize("seed", [5, 2_147_483_711])
@pytest.mark.parametrize("name", list(tiny.CUTS))
def test_bfloat16_control_fails_a_limit(name, seed):
    c = tiny.cell(name)
    cfg, traffic = c["config"], c["traffic"]
    ref_mod = H.load_module("reference", cfg["reference"])
    kw, kd = jax.random.split(H.seed_key(seed))
    params = ref_mod.make_params(cfg, kw)
    data = data_mod.make(traffic["data"], cfg, kd)
    batches = [data.batch(k) for k in range(traffic["compare_steps"])]
    want = T.reference_readings(cfg, traffic, seed, params, batches,
                                "float32")
    got = T.reference_readings(cfg, traffic, seed, params, batches,
                               "bfloat16", "bfloat16")
    read = T.compare(got, want)
    assert any(read[k] > c["limits"][k] for k in read), read

