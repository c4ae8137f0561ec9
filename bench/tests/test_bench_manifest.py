"""BENCHMARK.json's shape, and discovery of a cell's files by name."""
import copy
import json
import os
import re
import shutil
from types import SimpleNamespace

import pytest

from bench import run as R
from bench.lib import harness as H
from bench.lib import program
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bj():
    return H.manifest()


def test_names_and_units(bj):
    names = [c["name"] for c in bj["configs"]]
    names += [w["name"] for w in bj["workloads"]]
    names += [w["traffic"] for w in bj["workloads"]]
    names += [m["name"] for m in bj["end_to_end"] + bj["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    for m in bj["end_to_end"] + bj["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in bj["end_to_end"] + bj["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_keys_are_exactly_the_contract(bj):
    assert set(bj) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for c in bj["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bj["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in bj["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in bj["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_config_has_a_cell_and_four_chip_share(bj):
    used = {w["config"] for w in bj["workloads"]}
    assert used == {c["name"] for c in bj["configs"]}
    four = sum(w["chips"] == 4 for w in bj["workloads"])
    assert four <= max(1, len(bj["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in bj["workloads"])


def test_moves_target_is_reported_by_the_same_cells(bj):
    e2e = {m["name"]: m for m in bj["end_to_end"]}
    cells = [w["name"] for w in bj["workloads"]]
    assert "setup_s" in e2e
    for m in bj["per_layer"]:
        target = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in target.get("workloads", cells), (m["name"], w)
    for w in cells:
        assert any(w in m.get("workloads", cells) for m in bj["per_layer"])
        assert any(n != "setup_s" and w in m.get("workloads", cells)
                   for n, m in e2e.items())


def test_every_named_file_exists(bj):
    for c in bj["configs"]:
        cfg = H.load_json(H.ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(H.BENCH, "reference",
                                           f"{cfg['reference']}.py"))
    for w in bj["workloads"]:
        cell = H.cell(w["name"], bj)
        assert os.path.exists(os.path.join(
            H.BENCH, "runners", f"{cell['traffic']['runner']}.py"))
        for m in cell["per_layer"]:
            assert hasattr(H.load_module("metrics", m["name"]), "read")


def test_a_new_cell_is_found_by_name_with_no_edit(tmp_path, bj):
    """Copy the benchmark, add a configuration, a mix, a limits file and
    a metric as new files plus manifest entries, and find them all."""
    bench = tmp_path / "bench"
    shutil.copytree(H.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "mnist-autoencoder.json")
                     .read_text())
    cfg["name"] = "tiny-ae"
    (bench / "configs" / "tiny-ae.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "kfac-ae-8192.json")
                         .read_text())
    traffic["data"]["batch"] = 512
    (bench / "traffic" / "kfac-ae-512.json").write_text(json.dumps(traffic))
    (bench / "limits" / "tiny-ae-train.json").write_text(
        json.dumps({"loss": 1.0, "first_update": 1.0, "change": 1.0,
                    "last_update": 1.0}))
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    new = dict(bj)
    new["workloads"] = bj["workloads"] + [
        {"name": "tiny-ae-train", "config": "tiny-ae",
         "traffic": "kfac-ae-512", "chips": 1, "why": "test"}]
    new["per_layer"] = bj["per_layer"] + [
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "device", "moves": "train_step_ms",
         "workloads": ["tiny-ae-train"]}]
    cell = H.cell("tiny-ae-train", new, bench=str(bench))
    assert cell["config"]["name"] == "tiny-ae"
    assert cell["traffic"]["data"]["batch"] == 512
    assert "steps_seen" in [m["name"] for m in cell["per_layer"]]
    mod = H.load_module("metrics", "steps_seen", bench=str(bench))
    assert mod.read(type("C", (), {"steps": 7})) == 7.0
    after = {p: p.read_bytes() for p in before}
    assert after == before          # nothing that was there changed


def test_a_new_family_is_added_with_no_edit(tmp_path, bj):
    """Copy the benchmark and add a configuration of a new family as new
    files alone (its adapter and reference reuse the llama code under the
    new name), with a mix, limits and a tiny cut; run the tiny cell on the
    CPU, and change no byte that was there."""
    bench = tmp_path / "bench"
    shutil.copytree(H.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    fam, conf, mix, name = "newlm", "newlm-tiny", "kfac-newlm", "newlm-train"

    def copy_file(kind, src, dst, ext, **changes):
        text = (bench / kind / f"{src}.{ext}").read_text()
        if changes:
            text = json.dumps({**json.loads(text), **changes})
        (bench / kind / f"{dst}.{ext}").write_text(text)

    copy_file("adapters", "llama", fam, "py")
    copy_file("reference", "llama", fam, "py")
    copy_file("configs", "smollm-135m", conf, "json", name=conf, family=fam,
              reference=fam)
    copy_file("traffic", "kfac-lm-1x2048", mix, "json")
    copy_file("limits", "smollm-kfac-train", name, "json")
    copy_file("tiny", "smollm-kfac-train", name, "json")
    new = copy.deepcopy(bj)
    new["configs"].append({"name": conf, "source": "test", "reduced": [],
                           "file": f"bench/configs/{conf}.json",
                           "why": "test"})
    new["workloads"].append({"name": name, "config": conf, "traffic": mix,
                             "chips": 1, "why": "test"})
    for m in new["end_to_end"] + new["per_layer"]:
        if m["name"] in ("train_step_ms", "mfu.train"):
            m["workloads"].append(name)
    assert tiny.cuts(new, str(bench))[-1] == name
    c = tiny.cell(name, bench_json=new, bench=str(bench))
    assert c["config"]["family"] == fam
    device, peaks = tiny.cpu()
    res = R.run_cell(c, tiny.args(2_147_483_659), device, peaks)
    assert res["correct"], res["checks"]
    steps = res["attempted"]
    wall = 1e-3 * res["metrics"]["train_step_ms"]["value"] * steps
    ctx = SimpleNamespace(config=c["config"], traffic=c["traffic"],
                          bench=c["bench"], steps=steps, wall=wall,
                          n_devices=1, peaks=peaks)
    mfu = H.load_module("metrics", "mfu.train", str(bench)).read(ctx)
    assert mfu is not None and mfu > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before          # nothing that was there changed


def test_unknown_family_names_its_adapter_file():
    c = tiny.cell("smollm-kfac-train")
    cfg = dict(c["config"], family="no_such_family")
    with pytest.raises(FileNotFoundError,
                       match=r"bench/adapters/no_such_family\.py"):
        program.build(cfg, c["traffic"], 0)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        H.peaks_for("TPU v99")
    assert H.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
