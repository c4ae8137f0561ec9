"""Tiny cells for the CPU tests: each real cell's files, cut to a size a
test run holds by ``bench/tiny/<workload>.json`` (nested ``config`` and
``traffic`` overrides merged into the cell; the widths shrink here only,
the chip runs the files as they are).  A cell that ships such a file is
in the CPU tests with no edit here."""
import json
import os
import shutil
from types import SimpleNamespace

from bench.lib import harness as H


def cuts(bench_json: dict, bench: str = H.BENCH, chips: int = 1) -> list:
    """The manifest's workloads on ``chips`` chips that have a tiny cut, in
    manifest order.  A test process holds one CPU device: the cuts of
    cells on four chips run in a child process that forces four
    (``bench/tests/test_bench_mesh.py``)."""
    return [w["name"] for w in bench_json["workloads"]
            if w["chips"] == chips
            and os.path.exists(os.path.join(bench, "tiny",
                                            f"{w['name']}.json"))]


CUTS = cuts(H.manifest())
MESH_CUTS = cuts(H.manifest(), chips=4)


# a four-chip cell that the mesh tests add to a copy of the benchmark
PROBE = "mesh-probe"


def probe_root(dst: str) -> str:
    """``dst`` made into a copy of the checkout's ``BENCHMARK.json`` and
    ``bench/`` with one four-chip cell more, added as new files alone:
    the one-chip smollm mix on a (data 4, model 1) mesh with the sharded
    refresh, that cell's limits, and a tiny cut of four sequences."""
    bench = os.path.join(dst, "bench")
    shutil.copytree(H.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))

    def put(obj, *parts):
        with open(os.path.join(*parts), "w") as f:
            json.dump(obj, f)

    traffic = H.load_json(bench, "traffic", "kfac-lm-1x2048.json")
    traffic["mesh"] = {"data": 4, "model": 1}
    traffic["optimizer"].update(refresh_mode="sharded", kernel_backend="xla")
    put(traffic, bench, "traffic", f"{PROBE}.json")
    put(H.load_json(bench, "limits", "smollm-kfac-train.json"),
        bench, "limits", f"{PROBE}.json")
    cut = H.load_json(bench, "tiny", "smollm-kfac-train.json")
    cut["traffic"]["data"]["batch"] = 4
    put(cut, bench, "tiny", f"{PROBE}.json")
    bj = H.manifest()
    bj["workloads"].append({"name": PROBE, "config": "smollm-135m",
                            "traffic": PROBE, "chips": 4, "why": "test"})
    for m in bj["end_to_end"]:
        if m["name"] == traffic["step_metric"]:
            m["workloads"].append(PROBE)
    put(bj, dst, "BENCHMARK.json")
    return str(dst)


def merge(into: dict, over: dict) -> None:
    """Merge ``over`` into ``into``: nested dicts key by key, the rest
    replaced."""
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v


def cell(name: str, warm_steps: int = 1, bench_json: dict = None,
         bench: str = H.BENCH) -> dict:
    c = H.cell(name, bench_json or H.manifest(), bench)
    merge(c, H.load_json(bench, "tiny", f"{name}.json"))
    c["traffic"]["warm_steps"] = warm_steps
    return c


def args(seed: int, seconds: float = 0.3, trace: int = 0):
    return SimpleNamespace(seed=seed, seconds=seconds, trace=trace)


def cpu():
    import jax
    return jax.devices("cpu")[0], H.peaks_for("TPU v5 lite")


def on_four_devices(part: str, root: str = H.ROOT,
                    timeout: float = 1200) -> dict:
    """``bench/tests/mesh_cell.py <part> <root>`` in a child process on
    four forced CPU devices; its JSON line."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH, "tests", "mesh_cell.py"),
         part, root], env=env, cwd=H.ROOT, capture_output=True, text=True,
        timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh_cell.py {part} exited "
                           f"{proc.returncode}:\n{proc.stderr[-4000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4, out["devices"]
    return out
