"""Readings behind a training cell's limits, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [--first <seed>]
                               [--stand-in-seeds <m>]

For each of ``n`` seeds: the program's numbers (the cell's own set-up
path, at the cell's size) against the plain reference at full precision.
For the first ``m`` of them, the same comparison of these stand-ins:

* ``control``:        the plain reference put in the program's place,
                      its model computing in bfloat16 with its parameters
                      stored in bfloat16 (its factors, inverses and update
                      stay float32 at ``highest``);
* ``half_batch``:     the reference with half of every batch left out (of
                      its one sequence's positions, where a batch holds
                      one), the mean taken over the rest;
* ``ref_default``:    the reference at the TPU's default matmul precision
                      for float32, the precision the configuration states;
* ``ref_ns_default``: the reference at ``highest`` but for its
                      Newton–Schulz inverses, at the default precision;
* ``no_reduction``:   (a cell on a mesh) the program with each device's
                      gradients and factor statistics of its own rows
                      only (``bench/lib/faults.py``);
* ``no_lambda_rule``, ``no_gamma_sweep``, ``no_stale_inverses``: the
                      program with a schedule fault planted (λ never
                      adapted; no γ sweep; the inverses refreshed on every
                      step), where ``compare_steps`` reaches the step the
                      fault changes;
* a state left unchanged reads 1 on ``first_update`` and ``change`` by
  definition and needs no run.

Prints one JSON line per seed and a summary: for each number the largest
sound reading (the lower end of its limit) and the smallest reading of
each stand-in.  Refuses to run without a TPU, like the benchmark.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from bench.lib import harness as H  # noqa: E402

SCHEDULE_FAULTS = {"no_lambda_rule": {"t1": 0}, "no_gamma_sweep": {"t2": 0},
                   "no_stale_inverses": {"t3": 1}}


def half(batch):
    """Half of the batch: of its rows, or of its one row's positions."""
    axis = 0 if next(iter(batch.values())).shape[0] > 1 else 1
    return {k: jax.lax.slice_in_dim(v, 0, v.shape[axis] // 2, axis=axis)
            for k, v in batch.items()}


def faults_that_apply(opt: dict, steps: int) -> list:
    """The schedule faults that change one of the first ``steps`` steps."""
    out = []
    if 0 < opt["t1"] < steps:
        out.append("no_lambda_rule")
    if 0 < opt["t2"] < steps:
        out.append("no_gamma_sweep")
    sweep = lambda k: opt["t2"] > 0 and k % opt["t2"] == 0
    if opt["t3"] > 0 and any(k % opt["t3"] and not sweep(k)
                             for k in range(3, steps)):
        out.append("no_stale_inverses")
    return out


def _program(cell, seed, cache, fault=None):
    from bench.lib import faults, program
    from repro.configs.base import TrainConfig
    from repro.training.trainer import Trainer
    key = fault or "sound"
    if key not in cache:
        traffic = copy.deepcopy(cell["traffic"])
        traffic["optimizer"].update(SCHEDULE_FAULTS.get(fault, {}))
        cache[key] = program.build(cell["config"], traffic, H.seed31(seed))
        if fault in faults.PLANTED:
            faults.plant(fault, cache[key], cell["config"], traffic)
    prog = cache[key]
    prog.trainer = Trainer(prog.model, prog.opt,
                           TrainConfig(steps=0, seed=H.seed31(seed),
                                       log_every=1 << 30), prog.mesh,
                           obs=prog.obs)
    return prog


def readings_for_seed(cell, seed, cache, stand_ins: bool):
    from bench.runners import train as T
    from bench.lib import data as data_mod
    from bench.lib import program
    cfg, traffic = cell["config"], cell["traffic"]
    n = traffic["compare_steps"]
    ref_mod = H.load_module("reference", cfg["reference"])
    kw, kd = jax.random.split(H.seed_key(seed))
    params_ref = ref_mod.make_params(cfg, kw)
    data = data_mod.make(traffic["data"], cfg, kd,
                         mesh=program.mesh(traffic))
    batches = [data.batch(k) for k in range(n)]
    secs = {}
    t0 = time.perf_counter()
    got = T.program_readings(_program(cell, seed, cache), params_ref, data, n)
    secs["program"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = T.reference_readings(cfg, traffic, seed, params_ref, batches,
                                cfg["precision"]["params"])
    secs["reference"] = time.perf_counter() - t0
    out = {"seed": seed, "program": T.compare(got, want),
           "lam": {"program": got["lam"], "reference": want["lam"]},
           "gamma": {"program": got["gamma"], "reference": want["gamma"]},
           "seconds": secs}
    if stand_ins:
        f32 = cfg["precision"]["params"]
        runs = {
            "control": lambda: T.reference_readings(
                cfg, traffic, seed, params_ref, batches, "bfloat16",
                "bfloat16"),
            "half_batch": lambda: T.reference_readings(
                cfg, traffic, seed, params_ref, [half(b) for b in batches],
                f32),
            "ref_default": lambda: T.reference_readings(
                cfg, traffic, seed, params_ref, batches, f32,
                precision="default"),
            "ref_ns_default": lambda: T.reference_readings(
                cfg, traffic, seed, params_ref, batches, f32,
                ns_precision="default"),
        }
        planted = faults_that_apply(traffic["optimizer"], n)
        if "mesh" in traffic:
            planted.append("no_reduction")
        for fault in planted:
            runs[fault] = lambda f=fault: T.program_readings(
                _program(cell, seed, cache, f), params_ref, data, n)
        for name, fn in runs.items():
            t0 = time.perf_counter()
            r = fn()
            out[name] = T.compare(r, want)
            out["lam"][name] = r["lam"]
            out["gamma"][name] = r["gamma"]
            secs[name] = time.perf_counter() - t0
            gc.collect()
    del params_ref, data, batches
    gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--stand-in-seeds", type=int, default=4)
    ap.add_argument("--first", type=int, default=2_200_000_001)
    args = ap.parse_args(argv)
    cell = H.cell(args.workload, H.manifest())
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        H.say("no TPU; nothing run")
        return 3
    rows, cache = [], {}
    for i in range(args.seeds):
        row = readings_for_seed(cell, args.first + 7919 * i, cache,
                                i < args.stand_in_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    skip = ("seed", "program", "lam", "gamma", "seconds")
    stand_ins = [k for k in rows[0] if k not in skip]
    for num in rows[0]["program"]:
        summary[num] = {"lower": max(r["program"][num] for r in rows)}
        for k in stand_ins:
            summary[num][f"{k}_min"] = min(r[k][num] for r in rows if k in r)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
