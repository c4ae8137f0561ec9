"""General training runner: any configuration under any training mix.

Set-up builds one trainer (``bench/lib/program.py``) on weights and
batches the benchmark made from the seed, and drives it through
``Trainer.fit``: one step (the state after it holds the first update),
``compare_steps`` steps (their losses and the parameters they leave) and,
where that is fewer, ``warm_steps`` steps, so that every program of the
optimizer's schedule (refresh, λ step, γ sweep) has been compiled or
loaded from the cache.  The window is one more ``Trainer.fit`` call on
the same objects — each call starts the optimizer afresh, so the window
opens with its warm-up refreshes — stopped by the trainer's own SIGTERM
preemption hook once ``--seconds`` have passed.  After the window the
plain reference (``bench/lib/kfac_ref.py`` over the configuration's
``bench/reference/<name>.py``) follows the same ``compare_steps`` steps,
schedule included, from the same weights and batches, and the numbers
are compared.  On a traffic's mesh the batches stay sharded over its
``data`` axis for the reference too, so that its plain ``jnp`` runs
data-parallel on the cell's chips; its weights are replicated.
"""
from __future__ import annotations

import gc
import os
import shutil
import signal
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from bench.lib import data as data_mod
from bench.lib import harness as H
from bench.lib import kfac_ref, program
from bench.lib import trace as trace_mod


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(
        jnp.ravel(x).astype(jnp.float32))) for p, x in flat}


def worst_leaf_gap(got: dict, want: dict, keep) -> float:
    """Largest |‖got‖ − ‖want‖| over the kept leaves, each against the
    larger of its own reference norm and the median leaf's."""
    ref = sorted(want[k] for k in keep)
    med = ref[len(ref) // 2]
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keep)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, from per-leaf norms and losses."""
    grads = ref["grad_norms"]
    med = sorted(grads.values())[len(grads) // 2]
    keep = [k for k, g in grads.items() if g >= 1e-3 * med]
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(prog["losses"], ref["losses"]))
    return {"loss": loss,
            "first_update": worst_leaf_gap(prog["first_update"],
                                           ref["first_update"], keep),
            "change": worst_leaf_gap(prog["change"], ref["change"], keep),
            "last_update": worst_leaf_gap(prog["last_update"],
                                          ref["last_update"], keep)}


def reference_readings(cfg, traffic, seed, params_ref, batches, dtype,
                       param_dtype=None, precision="highest",
                       ns_precision=None, bench=H.BENCH):
    """Losses and per-leaf norms of the plain reference computing in
    ``dtype`` (parameters kept in ``param_dtype``, default float32) at
    matmul ``precision`` (the inverses at ``ns_precision``, where given),
    over as many steps as ``batches`` holds."""
    ref_mod = H.load_module("reference", cfg["reference"], bench)
    with jax.default_matmul_precision(precision):
        model = ref_mod.Model(cfg, dtype=getattr(jnp, dtype))
        out = kfac_ref.run(model, params_ref, batches, H.seed31(seed),
                           traffic["optimizer"], steps=len(batches),
                           param_dtype=getattr(jnp, param_dtype or "float32"),
                           ns_precision=ns_precision)
        return {"losses": out["losses"],
                "first_update": leaf_norms(out["first_update"]),
                "change": leaf_norms(out["change"]),
                "last_update": leaf_norms(out["last_update"]),
                "grad_norms": leaf_norms(out["first_grad"]),
                "lam": out["lam"], "gamma": out["gamma"]}


def program_readings(prog, params_ref, data, steps: int) -> dict:
    """The program's first update (from its state after one step), its
    losses over ``steps`` steps, the change they make and the update the
    last of them applied (from its state), per leaf, and the λ and γ its
    steps used."""
    noop = lambda *_: None
    params0 = prog.to_program(params_ref)
    out1 = prog.trainer.fit(params0, data, 1, log=noop)
    first = leaf_norms(prog.from_program(out1["state"].delta0))
    del out1
    out = prog.trainer.fit(params0, data, steps, log=noop)
    change = jax.tree.map(lambda a, b: a - b,
                          prog.from_program(out["params"]), params_ref)
    hist = out["history"]
    return {"losses": [h["loss"] for h in hist],
            "first_update": first, "change": leaf_norms(change),
            "last_update": leaf_norms(prog.from_program(out["state"].delta0)),
            "lam": [h.get("lam") for h in hist],
            "gamma": [h.get("gamma") for h in hist]}


def setup(cfg, traffic, seed, *, trace=False, bench=H.BENCH):
    """The cell's inputs, weights and trainer, and the program's readings
    of the first steps; every program of the schedule warmed.  The
    weights are not kept: ``weights()`` makes them again from the seed, so
    that during the window only the trainer holds them."""
    ref_mod = H.load_module("reference", cfg["reference"], bench)
    kw, kd = jax.random.split(H.seed_key(seed))
    weights = lambda: ref_mod.make_params(cfg, kw)
    data = data_mod.make(traffic["data"], cfg, kd, bench,
                         program.mesh(traffic))
    prog = program.build(cfg, traffic, H.seed31(seed), trace=trace,
                         bench=bench)
    readings = program_readings(prog, weights(), data,
                                traffic["compare_steps"])
    if traffic["warm_steps"] > traffic["compare_steps"]:
        prog.trainer.fit(prog.to_program(weights()), data,
                         traffic["warm_steps"], log=lambda *_: None)
    return SimpleNamespace(weights=weights, data=data, prog=prog,
                           readings=readings)


def window(s, seconds: float, trace_dir=None):
    """One ``Trainer.fit`` call stopped after ``seconds``; returns (wall
    seconds, steps, rejected steps, compiles inside, host clock)."""
    trainer = s.prog.trainer
    box = [s.prog.to_program(s.weights())]     # fit holds the only reference
    rejected0 = trainer.obs.counter("train/rejected_steps").value
    stop = threading.Timer(seconds, os.kill, (os.getpid(), signal.SIGTERM))
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with H.CompileClock() as clock, H.HostClock() as host:
        with jax.profiler.TraceAnnotation("bench/window"):
            t0 = time.perf_counter()
            stop.start()
            out = trainer.fit(box.pop(), s.data, 1 << 30,
                              log=lambda *_: None)
            t1 = time.perf_counter()
    stop.cancel()
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    if trace_dir:
        jax.profiler.stop_trace()
    steps = len(out["history"])
    rejected = trainer.obs.counter("train/rejected_steps").value - rejected0
    del out
    return t1 - t0, steps, int(rejected), clock, host


def memory_peak(devices):
    """The largest ``peak_bytes_in_use`` over ``devices`` (None where the
    backend reports none); on several, each device's goes to the log."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if len(peaks) > 1:
        H.say(f"peak_bytes_in_use per device: {peaks}")
    return None if None in peaks else max(peaks)


def run(ctx) -> dict:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    cfg, traffic, bench = cell["config"], cell["traffic"], cell["bench"]
    trace_dir = (os.path.join(H.ROOT, ".bench_trace", cell["workload"]["name"])
                 if ctx.trace else None)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    with H.CompileClock() as setup_clock:
        s = setup(cfg, traffic, seed, trace=ctx.trace, bench=bench)
    setup_s = time.perf_counter() - ctx.t_start
    H.say(f"setup_s={setup_s:.3f} (compile {setup_clock.seconds:.1f}s, "
          f"{setup_clock.compiles} compiles, {setup_clock.traces} traces)")
    wall, steps, rejected, clock, host = window(s, seconds, trace_dir)
    H.say(f"window: {steps} steps in {wall:.3f}s; compiles inside the "
          f"window: {clock.compiles} ({clock.traces} traces); host: "
          f"{host.cpu_s:.3f} CPU s, {host.preempted} involuntary switches, "
          f"steal {host.steal_s} s")
    devs = (list(s.prog.mesh.devices.flat) if s.prog.mesh is not None
            else jax.devices()[:1])
    out = {"attempted": steps, "failed": rejected,
           "memory_peak_bytes": memory_peak(devs),
           "end_to_end": {"setup_s": setup_s,
                          traffic["step_metric"]: 1000.0 * wall / max(steps, 1)}}
    if trace_dir:
        tr = trace_mod.load(trace_dir)
        lo, hi = trace_mod.window(tr["host"], "bench/window")
        red = trace_mod.reduce(tr, lo, hi)
        out["trace"] = SimpleNamespace(raw=tr, lo=lo, hi=hi, reduced=red,
                                       steps=steps, wall=wall)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state is freed before the reference runs
    readings, weights, data = s.readings, s.weights, s.data
    del s
    gc.collect()
    params_ref = weights()
    batches = [data.batch(k) for k in range(traffic["compare_steps"])]
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, traffic, seed, params_ref, batches,
                             cfg["precision"]["params"], bench=bench)
    H.say(f"reference: {time.perf_counter() - t_ref:.1f}s")
    out["checks"] = compare(readings, ref)
    return out
