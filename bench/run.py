"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, limits and per-layer readers are
found by the names in ``BENCHMARK.json`` (``bench/lib/harness.py``).
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and it carries the per-layer metrics.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced) and, last, ``checks``: each compared number with its limit.
Without a TPU, with fewer chips than the cell asks for, or on a device
kind missing from ``bench/peaks.json``, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.lib import harness as H  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def check_device(chips: int):
    """(device, peaks) or SystemExit: a TPU with enough chips whose kind
    is in the peaks table, or nothing runs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        H.say(f"no TPU: jax.devices()[0] is {devs[0].platform}; nothing run")
        raise SystemExit(3)
    if len(devs) < chips:
        H.say(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
        raise SystemExit(3)
    try:
        return devs[0], H.peaks_for(devs[0].device_kind)
    except KeyError as e:
        H.say(str(e))
        raise SystemExit(3)


def checks_of(out: dict, limits: dict) -> dict:
    return {name: {"value": float(v), "limit": float(limits[name])}
            for name, v in out["checks"].items()}


def is_correct(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def per_layer(cell, out, peaks, device, n_dev):
    tr = out["trace"]
    ctx = SimpleNamespace(config=cell["config"], traffic=cell["traffic"],
                          bench=cell["bench"], peaks=peaks, trace=tr.raw,
                          lo=tr.lo, hi=tr.hi, reduced=tr.reduced,
                          n_devices=n_dev,
                          **{k: v for k, v in vars(tr).items()
                             if k not in ("raw", "lo", "hi", "reduced")})
    metrics = {}
    for m in cell["per_layer"]:
        v = H.load_module("metrics", m["name"], cell["bench"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics


def run_cell(cell, args, device, peaks) -> dict:
    """Everything after the look for a chip: set-up, window, reference,
    comparison; returns the result line as a dict."""
    chips = cell["workload"]["chips"]
    runner = H.load_module("runners", cell["traffic"]["runner"],
                           cell["bench"])
    out = runner.run(SimpleNamespace(cell=cell, seed=args.seed,
                                     seconds=args.seconds,
                                     trace=bool(args.trace),
                                     t_start=T_START))
    checks = checks_of(out, cell["limits"])
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    if args.trace:
        metrics = per_layer(cell, out, peaks, device, chips)
    else:
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out["end_to_end"].items() if k in units}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": chips, "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": is_correct(checks), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        red = out["trace"].reduced
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        H.say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    bench_json = H.manifest()
    cell = H.cell(args.workload, bench_json)
    chips = cell["workload"]["chips"]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device, peaks = check_device(chips)
    H.say(f"{args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={device.device_kind} cache={cache}")
    result = run_cell(cell, args, device, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
