"""The tiny cuts of the cells on four chips, run on four CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/mesh_cell.py <sound|faults> [<root>]

A test process holds one CPU device, so ``bench/tests/test_bench_mesh.py``
(``sound``) and ``bench/tests/test_bench_mesh_faults.py`` (``faults``)
start this in a child process and read the one JSON line it prints, by
cut.  ``sound``: the sound run's result line, through ``program.build``
and the reference; the bfloat16 control's readings; and whether the
targets the program's head samples from logits sharded over ``data`` are
the ones drawn from the same logits on one device.  ``faults``: the
result line of each run with a fault planted (``bench/lib/faults.py``).
``root`` holds the ``BENCHMARK.json`` and ``bench/`` whose four-chip cells
run (default: this checkout).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run as R  # noqa: E402
from bench.lib import data as data_mod  # noqa: E402
from bench.lib import faults, program  # noqa: E402
from bench.lib import harness as H  # noqa: E402
from bench.runners import train as T  # noqa: E402
from bench.tests import tiny  # noqa: E402

FAULTS = ("half_batch", "state_unchanged", "no_reduction")
SEED = 2_147_483_659
WHERE = {"bench_json": None, "bench": H.BENCH}   # set by main


def cut(name):
    return tiny.cell(name, **WHERE)


def run(name, fault=None):
    build = program.build
    if fault is not None:
        program.build = faults.broken_build(fault, build)
    try:
        device, peaks = tiny.cpu()
        return R.run_cell(cut(name), tiny.args(SEED), device, peaks)
    finally:
        program.build = build


def control(name, seed):
    """The bfloat16 control against the float32 reference, on the cut's
    mesh-sharded batches."""
    c = cut(name)
    cfg, traffic = c["config"], c["traffic"]
    ref_mod = H.load_module("reference", cfg["reference"], c["bench"])
    kw, kd = jax.random.split(H.seed_key(seed))
    params = ref_mod.make_params(cfg, kw)
    data = data_mod.make(traffic["data"], cfg, kd, c["bench"],
                         program.mesh(traffic))
    batches = [data.batch(k) for k in range(traffic["compare_steps"])]
    want = T.reference_readings(cfg, traffic, seed, params, batches,
                                "float32")
    got = T.reference_readings(cfg, traffic, seed, params, batches,
                               "bfloat16", "bfloat16")
    return T.compare(got, want)


def sampled_targets_agree(name):
    """The program's head draws ``jax.random.categorical`` from (B, c, V)
    logits sharded over ``data``; the reference draws from the same
    logits.  The draws must not depend on the sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    traffic = cut(name)["traffic"]
    mesh = program.mesh(traffic)
    b = traffic["data"]["batch"]
    key, k2 = jax.random.split(jax.random.PRNGKey(SEED % 997))
    logits = jax.random.normal(k2, (b, 128, 512))
    draw = jax.jit(lambda k, z: jax.random.categorical(k, z, axis=-1))
    one = draw(key, logits)
    spread = draw(key, jax.device_put(logits, NamedSharding(mesh, P("data"))))
    return bool(jax.config.jax_threefry_partitionable
                and jnp.array_equal(one, spread))


def sound(name):
    return {"sound": run(name), "control": control(name, 2_147_483_711),
            "sampled_targets_agree": sampled_targets_agree(name)}


def broken(name):
    return {f: run(name, f) for f in FAULTS}


def main(part, root=H.ROOT):
    bench = os.path.join(root, "bench")
    WHERE.update(bench_json=H.manifest(root), bench=bench)
    out = {"devices": len(jax.devices())}
    for name in tiny.cuts(WHERE["bench_json"], bench, chips=4):
        out[name] = {"sound": sound, "faults": broken}[part](name)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
