"""What every cell shares: the manifest, discovery of a cell's files by
name, the device check, compile accounting and the result line.

Files are found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``   sizes, source, precision, reference;
* ``bench/traffic/<traffic>.json``  the mix: data, optimizer, runner;
* ``bench/limits/<workload>.json``  each compared number's limit;
* ``bench/metrics/<metric>.py``     a per-layer metric's reader;
* ``bench/reference/<name>.py``     a configuration's plain reference;
* ``bench/runners/<runner>.py``     the general runner a traffic names;
* ``bench/adapters/<family>.py``    a configuration family's model, weight
                                    layout and training FLOPs;
* ``bench/inputs/<kind>.py``        the batches of a traffic's data kind;
* ``bench/tiny/<workload>.json``    a cell's cut for the CPU tests.

Each lookup takes the root it searches (``bench``), so that a test can
run a copy of the benchmark.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def load_module(kind: str, name: str, bench: str = BENCH):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold ``.``
    and ``-``, which a package import would not take)."""
    path = os.path.join(bench, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench_json: dict, bench: str = BENCH) -> dict:
    """Everything one workload needs, found by name under ``bench``,
    which the cell keeps for the lookups that follow."""
    work = {w["name"]: w for w in bench_json["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = load_json(bench, "configs", f"{w['config']}.json")
    traffic = load_json(bench, "traffic", f"{w['traffic']}.json")
    limits = load_json(bench, "limits", f"{name}.json")
    mesh = math.prod(traffic.get("mesh", {}).values())
    if mesh != w["chips"]:
        raise ValueError(f"workload {name!r} asks for {w['chips']} chips; "
                         f"its traffic's mesh spans {mesh}")
    e2e = [m for m in bench_json["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench_json["per_layer"]
                 if name in m.get("workloads", [name])]
    return dict(workload=w, config=cfg, traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=per_layer, bench=bench)


def peaks_for(kind: str, bench: str = BENCH) -> dict:
    table = load_json(bench, "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits seed
    it and the rest are folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def seed31(seed: int) -> int:
    """The seed folded into 31 bits, for APIs that take a small int."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF


class CompileClock:
    """Seconds and count of JAX's compilations inside the ``with`` block,
    from its own monitoring events (copied from ``chip_smoke.py``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.COMPILE:
            self.compiles += 1
        if event == self.EVENTS[0]:
            self.traces += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class HostClock:
    """What the host did inside the ``with`` block: this process's CPU
    seconds and involuntary context switches, and the machine's CPU time
    stolen by its hypervisor (``/proc/stat``, in seconds, where readable)."""

    @staticmethod
    def _read():
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        steal = None
        try:
            with open("/proc/stat") as f:
                cpu = f.readline().split()
            steal = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            pass
        return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, steal

    def __enter__(self):
        self._start = self._read()
        return self

    def __exit__(self, *exc):
        end = self._read()
        self.cpu_s = end[0] - self._start[0]
        self.preempted = end[1] - self._start[1]
        self.steal_s = (None if end[2] is None or self._start[2] is None
                        else end[2] - self._start[2])


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
