"""The harness runs a cell on a device mesh, and a traffic file without
``mesh`` builds what it built before the mesh came.

The tiny cut of each cell on four chips, and of one more added to a copy
of the benchmark as new files alone (``tiny.probe_root``), runs whole on
four CPU devices, in a child process (``bench/tests/mesh_cell.py
sound``): the program
built on the traffic's mesh through ``program.build``, the weights put on
it with the model's parameter shardings, the batches sharded over
``data``, the reference data-parallel on the same batches.  Its faults
are in ``bench/tests/test_bench_mesh_faults.py``.
"""
from types import SimpleNamespace

import jax
import pytest

from bench.lib import data as data_mod
from bench.lib import harness as H
from bench.lib import program
from bench.runners import train as T
from bench.tests import tiny

MESH_CUTS = list(tiny.MESH_CUTS) + [tiny.PROBE]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.probe_root(tmp_path_factory.mktemp("mesh"))


@pytest.fixture(scope="module")
def four(root):
    return tiny.on_four_devices("sound", root)


def test_the_four_chip_cell_has_a_tiny_cut(root):
    """Every four-chip cell has a tiny cut, and one added as files alone
    is found with no edit."""
    four = [w["name"] for w in H.manifest()["workloads"] if w["chips"] == 4]
    assert tiny.MESH_CUTS == four
    bench = f"{root}/bench"
    assert tiny.cuts(H.manifest(root), bench, chips=4) == MESH_CUTS
    assert tiny.cuts(H.manifest(root), bench) == tiny.CUTS


@pytest.mark.parametrize("name", MESH_CUTS)
def test_sound_run_on_four_devices_is_correct(four, name):
    res = four[name]["sound"]
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("name", MESH_CUTS)
def test_bfloat16_control_on_four_devices_fails_a_limit(four, root, name):
    limits = H.load_json(root, "bench", "limits", f"{name}.json")
    read = four[name]["control"]
    assert any(read[k] > limits[k] for k in read), read


@pytest.mark.parametrize("name", MESH_CUTS)
def test_sampled_targets_do_not_depend_on_the_sharding(four, name):
    assert four[name]["sampled_targets_agree"]


@pytest.mark.parametrize("name", list(tiny.CUTS))
def test_traffic_without_mesh_builds_no_mesh(name):
    c = tiny.cell(name)
    cfg, traffic = c["config"], c["traffic"]
    assert "mesh" not in traffic and program.mesh(traffic) is None
    prog = program.build(cfg, traffic, 0)
    assert prog.mesh is None and prog.trainer.mesh is None
    assert prog.opt.engine.mesh is None
    assert getattr(prog.model, "mesh", None) is None
    fam = program.adapter(cfg)
    # the adapter's own move of weights, with no placing around it
    assert (prog.to_program.__module__, prog.to_program.__qualname__) == \
        (fam.to_program.__module__, fam.to_program.__qualname__)
    data = data_mod.make(traffic["data"], cfg, H.seed_key(3))
    kind = H.load_module("inputs", traffic["data"]["kind"])
    assert not isinstance(data, data_mod.OnMesh)
    assert type(data).__name__ == type(kind.make(
        traffic["data"], cfg, H.seed_key(3))).__name__


@pytest.mark.parametrize("name", list(tiny.CUTS))
def test_traffic_without_mesh_builds_the_same_programs(name):
    """The statistics and update programs lowered from ``program.build``
    are the ones a meshless build by hand gives, as the harness built
    them before a traffic could name a mesh."""
    from repro import obs as obs_mod
    from repro import optimizers
    from repro.configs.base import ObsConfig
    c = tiny.cell(name)
    cfg, traffic = c["config"], c["traffic"]
    opt_spec = traffic["optimizer"]
    ocfg = ObsConfig(enabled=False, trace_annotations=False)
    kcfg = program.kfac_config(opt_spec, ocfg)
    fam = program.adapter(cfg)
    by_hand = optimizers.kfac(
        fam.model(cfg, kcfg, cfg["precision"]["compute"]), kcfg, None,
        family=opt_spec.get("family", "categorical"),
        obs=obs_mod.Obs(ocfg))
    built = program.build(cfg, traffic, 0).opt
    ref_mod = H.load_module("reference", cfg["reference"])
    kw, kd = jax.random.split(H.seed_key(7))
    params = fam.to_program(ref_mod.make_params(cfg, kw))
    batch = data_mod.make(traffic["data"], cfg, kd).batch(0)
    rng = jax.random.PRNGKey(0)

    def texts(opt):
        pipe = opt.update.__self__
        state = opt.init(params, batch)
        stats = pipe._stats.lower(state, params, batch, rng)
        _, grads, _ = pipe._stats(state, params, batch, rng)
        update = pipe._update.lower(state, params, grads, batch, rng)
        return stats.as_text(), update.as_text()

    assert texts(built) == texts(by_hand)


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_is_the_fullest_device():
    devs = [_Dev({"peak_bytes_in_use": n}) for n in (5, 9, 7, 1)]
    assert T.memory_peak(devs) == 9
    assert T.memory_peak(devs[:1]) == 5
    assert T.memory_peak([_Dev(None)]) is None
    assert T.memory_peak(devs + [_Dev({})]) is None


def test_mesh_is_built_from_the_traffic():
    traffic = {"mesh": {"data": 1, "model": 1}}
    mesh = program.mesh(traffic)
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == jax.devices()[:1]
    data = data_mod.OnMesh(SimpleNamespace(
        batch=lambda k: {"tokens": jax.numpy.full((2, 4), k)}), 3, mesh)
    assert [int(data.batch(k)["tokens"][0, 0]) for k in range(4)] == \
        [0, 1, 2, 0]
    assert data.batch(0)["tokens"].sharding.spec == \
        jax.sharding.PartitionSpec("data")


@pytest.mark.parametrize("name", [w["name"] for w in H.manifest()["workloads"]])
def test_a_cells_chips_are_its_traffic_meshs(name):
    """A cell loads only where its traffic's mesh spans the chips it asks
    for (one without a mesh)."""
    bj = H.manifest()
    c = H.cell(name, bj)
    w = dict(c["workload"], chips=c["workload"]["chips"] * 2)
    with pytest.raises(ValueError, match="mesh spans"):
        H.cell(name, dict(bj, workloads=[w]))
