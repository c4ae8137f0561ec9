"""The plain reference follows the program's K-FAC schedule step by step.

At a tiny cut of each cell on the CPU, where the program computes in full
float32 as the reference does, the two take the same λ (the
Levenberg–Marquardt rule every T1 steps) and the same γ (the sweep every
T2 steps) on every compared step, and their compared numbers agree to
rounding.  ``bench/calibrate.py``'s schedule faults are chosen by the
steps a cell compares.
"""
import jax
import pytest

from bench import calibrate as C
from bench.lib import data as data_mod
from bench.lib import harness as H
from bench.lib import program
from bench.runners import train as T
from bench.tests import tiny


@pytest.mark.parametrize("name", list(tiny.CUTS))
def test_reference_takes_the_programs_lambda_and_gamma(name):
    c = tiny.cell(name)
    cfg, traffic = c["config"], c["traffic"]
    n = traffic["compare_steps"]
    seed = 2_147_483_659
    ref_mod = H.load_module("reference", cfg["reference"])
    kw, kd = jax.random.split(H.seed_key(seed))
    params = ref_mod.make_params(cfg, kw)
    data = data_mod.make(traffic["data"], cfg, kd)
    prog = program.build(cfg, traffic, H.seed31(seed))
    got = T.program_readings(prog, params, data, n)
    want = T.reference_readings(cfg, traffic, seed, params,
                                [data.batch(k) for k in range(n)], "float32")
    # the program's history holds the λ a step used; the reference's, the
    # λ after the step's rule
    assert got["lam"][1:] == pytest.approx(want["lam"][:-1], rel=1e-5)
    assert got["gamma"] == pytest.approx(want["gamma"], rel=1e-5)
    opt = traffic["optimizer"]
    assert len(set(want["lam"])) > 1               # the rule moved λ
    if opt["t2"] < n:
        assert want["gamma"][-1] != want["gamma"][0]   # the sweep moved γ
    read = T.compare(got, want)
    assert max(read.values()) < 1e-4, read


def test_schedule_faults_follow_the_compared_steps():
    opt = dict(t1=5, t2=20, t3=5)
    assert C.faults_that_apply(opt, 6) == ["no_lambda_rule",
                                           "no_stale_inverses"]
    assert C.faults_that_apply(opt, 21) == ["no_lambda_rule",
                                            "no_gamma_sweep",
                                            "no_stale_inverses"]
    assert C.faults_that_apply(opt, 3) == []
    assert C.faults_that_apply(dict(t1=5, t2=20, t3=1), 21) == [
        "no_lambda_rule", "no_gamma_sweep"]
