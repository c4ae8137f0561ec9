"""Tiny cells for the CPU tests: each real cell's files, cut to a size a
test run holds by ``bench/tiny/<workload>.json`` (nested ``config`` and
``traffic`` overrides merged into the cell; the widths shrink here only,
the chip runs the files as they are).  A cell that ships such a file is
in the CPU tests with no edit here."""
import os
from types import SimpleNamespace

from bench.lib import harness as H


def cuts(bench_json: dict, bench: str = H.BENCH) -> list:
    """The manifest's workloads that have a tiny cut, in manifest order."""
    return [w["name"] for w in bench_json["workloads"]
            if os.path.exists(os.path.join(bench, "tiny",
                                           f"{w['name']}.json"))]


CUTS = cuts(H.manifest())


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
