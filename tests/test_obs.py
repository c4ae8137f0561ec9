"""Observability (repro.obs): metrics exactness, schema round-trip, the
disabled-is-bitwise-identical contract, and the shared latency split.

The load-bearing pins:

* **Disabled parity** — training with ``ObsConfig(enabled=False)`` (the
  default) produces bitwise-identical params/history to an enabled run,
  and the *same jitted program counts* per stage: instrumentation lives
  entirely host-side (spans block on outputs the host would eventually
  sync anyway; counters are plain host ints), so the compiled graphs
  cannot differ.  Ditto for serving tokens and the decode step's jit
  cache.
* **Exact percentiles** — ``Histogram.percentile`` must bit-match
  ``numpy.percentile`` (linear interpolation) over the bounded
  most-recent-N reservoir window.
* **Schema** — every event written through the sink round-trips through
  ``read_jsonl``'s validator, and ``benchmarks/obs_check.py`` (the CI
  gate) accepts/rejects correctly.
* **Thread safety** — concurrent writers from daemon threads (the
  OverlapController / BundleWriter pattern) never drop an increment or
  interleave a JSONL line.
"""
import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optimizers
from repro.configs.base import KFACConfig, TrainConfig
from repro.data.pipeline import SyntheticAutoencoderData
from repro.models.mlp import MLP
from repro.obs import (NULL_SPAN, Obs, ObsConfig, Registry,
                       RequestLatencyTracker, console_summary, percentile,
                       read_jsonl, validate_event)
from repro.obs.export import JsonlSink
from repro.training.trainer import Trainer

DIMS = (20, 12, 8, 12, 20)


def _problem(n=128):
    mlp = MLP(list(DIMS), nonlin="tanh", loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    data = SyntheticAutoencoderData(DIMS[0], 8, n, seed=3)
    return mlp, params, data


# ---------------------------------------------------------------------------
# metrics: exact percentiles, labels, snapshots
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy():
    rs = np.random.RandomState(0)
    xs = list(rs.lognormal(size=257))
    for q in (0, 25, 50, 90, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), abs=0.0, rel=1e-12)


def test_histogram_percentiles_windowed():
    """p50/p99 are exact over the bounded most-recent-N window, matching
    numpy's linear interpolation — including once the reservoir rolls."""
    reg = Registry(reservoir=64)
    h = reg.histogram("lat_s")
    rs = np.random.RandomState(1)
    xs = rs.exponential(size=200)
    for x in xs:
        h.observe(float(x))
    window = xs[-64:]                       # most recent N survive
    for q in (50, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(window, q)), rel=1e-12)
    snap = h.snapshot()
    assert snap["count"] == 200             # totals cover ALL observations
    assert snap["sum"] == pytest.approx(float(xs.sum()))
    assert snap["max"] == pytest.approx(float(xs.max()))
    assert snap["p50"] == pytest.approx(float(np.percentile(window, 50)))


def test_registry_labels_and_kind_clash():
    reg = Registry()
    c1 = reg.counter("hits", {"route": "a"})
    c2 = reg.counter("hits", {"route": "b"})
    assert c1 is not c2
    assert reg.counter("hits", {"route": "a"}) is c1   # get-or-create
    c1.inc(); c1.inc(3)
    assert c1.value == 4
    with pytest.raises(ValueError):
        c1.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("hits", {"route": "a"})   # same name+labels, other kind


# ---------------------------------------------------------------------------
# exporters: JSONL schema, console
# ---------------------------------------------------------------------------

def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    sink.write("train_step", {"step": 0, "loss": 2.5, "wall_s": 0.01})
    sink.write("kfac_step", {"step": 0, "stages": {"estimate_stats": 1e-3}})
    sink.write("refresh", {"mode": "serial", "wall_s": 2e-3})
    sink.write("serve_request", {"uid": 7, "n_tokens": 12,
                                 "ttft_ms": 30.0})
    sink.write("serve_run", {"steps": 40, "completed": 3})
    sink.write("custom_event", {"anything": [1, 2.5, "x", None]})
    sink.close()
    events = read_jsonl(path)
    assert [e["event"] for e in events] == [
        "train_step", "kfac_step", "refresh", "serve_request",
        "serve_run", "custom_event"]
    assert all(e["v"] == 1 and e["ts"] > 0 for e in events)

    # the CI gate accepts the file and counts types
    from benchmarks import obs_check
    counts = obs_check.check(path, expect=["train_step", "refresh"])
    assert counts["train_step"] == 1
    with pytest.raises(ValueError, match="never emitted"):
        obs_check.check(path, expect=["no_such_event"])


def test_jsonl_rejects_bad_events(tmp_path):
    assert validate_event({"v": 1, "event": "refresh", "ts": 1.0,
                           "mode": "serial", "wall_s": 0.1})
    with pytest.raises(ValueError, match="schema v"):
        validate_event({"v": 99, "event": "x", "ts": 1.0})
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"v": 1, "event": "train_step", "ts": 1.0})
    with pytest.raises(ValueError, match="non-finite"):
        validate_event({"v": 1, "event": "x", "ts": 1.0,
                        "bad": float("inf")})
    # a malformed line fails read_jsonl with its line number
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"v": 1, "event": "refresh", "ts": 1.0,
                            "mode": "serial", "wall_s": 0.1}) + "\n")
        f.write("{\"v\": 1}\n")
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        read_jsonl(path)


def test_console_render():
    reg = Registry()
    reg.counter("serve/steps").inc(5)
    reg.counter("train/host_syncs", {"site": "kfac/read_step"}).inc(3)
    reg.gauge("train/loss", {"arch": "mlp"}).set(1.25)
    h = reg.histogram("span_s", {"span": "kfac/estimate_stats"})
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    reg.histogram("span_s", {"span": "never/observed"})
    text = console_summary(reg, title="t")
    assert "[t] serve/steps = 5" in text
    assert "[t] train/host_syncs{site=kfac/read_step} = 3" in text
    assert "[t] train/loss{arch=mlp} = 1.25" in text
    assert "span_s{span=kfac/estimate_stats}: n=3 mean=0.2" in text
    assert "p50=0.2" in text and "p99" in text
    assert "never/observed" not in text


# ---------------------------------------------------------------------------
# the disabled-parity pin: training
# ---------------------------------------------------------------------------

def _jit_cache_sizes(pipe):
    out = {"stats": pipe._stats._cache_size(),
           "update": pipe._update._cache_size(),
           "update3": pipe._update3._cache_size(),
           "refresh": pipe._refresh._cache_size(),
           "lambda": pipe._lambda._cache_size()}
    return out


def _duration_listeners():
    from jax._src import monitoring
    return list(monitoring.get_event_duration_listeners())


def test_training_disabled_bitwise_parity(tmp_path):
    """Enabled-vs-disabled training is bitwise identical (params AND the
    full scalar history) and compiles the same number of programs per
    stage — telemetry must never touch the jitted computation — and
    ``fit`` leaves no compile listener registered behind it."""
    steps = 8
    results, cache_sizes = [], []
    listeners = _duration_listeners()
    for enabled in (False, True):
        mlp, params, data = _problem()
        ocfg = ObsConfig(enabled=enabled,
                         jsonl_path=(str(tmp_path / "train.jsonl")
                                     if enabled else ""))
        cfg = KFACConfig(lambda_init=3.0, t1=2, t2=4, t3=3, eta=1e-5,
                         obs=ocfg)
        obs = Obs(ocfg)
        opt = optimizers.kfac(mlp, cfg, family="bernoulli", obs=obs)
        tr = Trainer(mlp, opt, TrainConfig(steps=steps, seed=0,
                                           log_every=10 ** 9, obs=ocfg),
                     obs=obs)
        out = tr.fit(params, data, steps, log=lambda *_: None)
        obs.close()
        assert _duration_listeners() == listeners
        results.append(out)
        # the Optimizer wraps the pipeline's bound methods
        pipe = opt.update.__self__
        cache_sizes.append(_jit_cache_sizes(pipe))

    off, on = results
    for a, b in zip(jax.tree.leaves(off["params"]),
                    jax.tree.leaves(on["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert off["history"] == on["history"]
    assert cache_sizes[0] == cache_sizes[1], (
        "obs=enabled changed what got compiled")

    # and the enabled run really did log the pipeline + trainer planes
    events = read_jsonl(str(tmp_path / "train.jsonl"))
    kinds = {e["event"] for e in events}
    assert {"train_step", "kfac_step", "refresh"} <= kinds
    ks = [e for e in events if e["event"] == "kfac_step"]
    assert len(ks) == steps
    assert all("estimate_stats" in e["stages"] for e in ks)


def test_trainer_counts_rejected_steps():
    """The rejected-step counter is live even with obs disabled (counters
    are plain host ints feeding run summaries)."""
    mlp, params, data = _problem()
    cfg = KFACConfig(lambda_init=3.0, t3=3, eta=1e-5)
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    obs = Obs()                              # disabled
    tr = Trainer(mlp, opt, TrainConfig(steps=4, seed=0, log_every=10 ** 9),
                 obs=obs)
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), params)
    tr.fit(bad, data, 2, log=lambda *_: None)
    assert obs.registry.counter("train/rejected_steps").value >= 1
    assert obs.registry.counter("train/steps").value >= 2


# ---------------------------------------------------------------------------
# the loop's sync sites: spans, read counts, compiles, named programs
# ---------------------------------------------------------------------------

T1 = 2
SITES = ("train/step_inputs", "kfac/read_step", "train/finite_check",
         "train/metrics_to_host", "train/emit")


def _kfac_fit(steps, ocfg, **kw):
    """A tiny K-FAC fit (λ rule every T1, γ sweep every 4, refresh every
    3); returns (fit result, Obs, pipeline)."""
    mlp, params, data = _problem()
    cfg = KFACConfig(lambda_init=3.0, t1=T1, t2=4, t3=3, eta=1e-5, obs=ocfg,
                     **kw)
    obs = Obs(ocfg)
    opt = optimizers.kfac(mlp, cfg, family="bernoulli", obs=obs)
    tr = Trainer(mlp, opt, TrainConfig(steps=steps, seed=0,
                                       log_every=10 ** 9, obs=ocfg), obs=obs)
    out = tr.fit(params, data, steps, log=lambda *_: None)
    obs.close()
    return out, obs, opt.update.__self__


@pytest.fixture
def reads(monkeypatch):
    """Counts device->host conversions of jax arrays (``bool``, ``int``,
    ``float``, ``__index__``, ``__array__``, ``item``; one
    ``jax.device_get`` of a whole tree is one read) and calls of
    ``jax.block_until_ready``."""
    from jax._src.array import ArrayImpl
    n = {"read": 0, "block": 0}
    inside = []                  # conversions made by a device_get

    def counted(fn, key):
        def f(*a, **k):
            if not inside:
                n[key] += 1
            inside.append(fn)
            try:
                return fn(*a, **k)
            finally:
                inside.pop()
        return f

    for name in ("__bool__", "__int__", "__float__", "__index__",
                 "__array__", "item"):
        monkeypatch.setattr(ArrayImpl, name,
                            counted(getattr(ArrayImpl, name), "read"))
    monkeypatch.setattr(jax, "device_get", counted(jax.device_get, "read"))
    monkeypatch.setattr(jax, "block_until_ready",
                        counted(jax.block_until_ready, "block"))
    return n


def test_site_spans_in_profiler_trace(tmp_path):
    """Under the profiler the site spans land in the host plane: the step
    inputs, the metric transfer and the event once a step, the step read
    once a fit, no finite check and no λ guard (the update programs guard
    on the device).  Each step's transfer comes after the next step's
    dispatch: the metrics run one step behind."""
    import glob
    steps = 5
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _kfac_fit(steps, ObsConfig(enabled=True, trace_annotations=True))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    want = {"train/step_inputs": steps, "kfac/read_step": 1,
            "train/finite_check": 0, "train/metrics_to_host": steps,
            "kfac/lambda_guard": 0, "train/emit": steps}
    assert {site: len(spans.get(site, [])) for site in want} == want
    step, read = sorted(spans["train/step"]), sorted(
        spans["train/metrics_to_host"])
    for k in range(steps - 1):
        # row k is read after step k + 1 is dispatched, before step k + 2
        assert step[k + 1][1] <= read[k][0]
        assert k + 2 == steps or read[k][1] <= step[k + 2][0]


def test_site_spans_add_no_block_or_read(reads, monkeypatch):
    """The site spans carry no ``block``: with them live, the enabled fit
    makes the same ``block_until_ready`` calls and device reads as with
    them replaced by the no-op span — one step read and one metric
    transfer a step."""
    steps = 6
    counts = []
    for live in (True, False):
        if not live:
            span = Obs.span
            monkeypatch.setattr(
                Obs, "span", lambda self, name, block=None:
                NULL_SPAN if name in SITES else span(self, name, block))
        reads.update(read=0, block=0)
        _kfac_fit(steps, ObsConfig(enabled=True))
        counts.append(dict(reads))
    assert counts[0] == counts[1]
    assert counts[0]["block"] > 0 and counts[0]["read"] == 1 + steps


def test_host_syncs_count_the_schedules_reads(reads):
    """``train/host_syncs{site}`` is live with obs disabled and counts
    each site's reads: one step read per fit and one transfer of a
    step's metrics per step; no finite-check or λ-guard read (the guard
    runs on the device, every step) — every device read the loop
    makes."""
    steps = 6
    out, obs, _ = _kfac_fit(steps, ObsConfig())
    got = {m.labels[0][1]: m.value
           for m in obs.registry.find("train/host_syncs")}
    assert got == {"kfac/read_step": 1, "train/finite_check": 0,
                   "train/metrics_to_host": steps}
    assert sum(got.values()) == reads["read"]
    assert obs.registry.counter("train/device_guard_steps").value == steps
    assert len(out["history"]) == steps


def test_train_step_events_carry_syncs_and_compiles(tmp_path):
    """Each ``train_step`` event carries the step's reads and backend
    compiles: the transfer of its metrics (and, on the first step, the
    step read); compiles on the first step, none once every program of
    the period (refresh, λ step, γ sweep) has run."""
    steps = 9
    path = str(tmp_path / "train.jsonl")
    out, obs, _ = _kfac_fit(steps, ObsConfig(enabled=True, jsonl_path=path))
    ev = [e for e in read_jsonl(path) if e["event"] == "train_step"]
    assert [e["step"] for e in ev] == list(range(steps))
    assert [e["host_syncs"] for e in ev] == [
        1 + (k == 0) for k in range(steps)]
    assert ev[0]["compiles"] > 0
    assert all(e["compiles"] == 0 for e in ev[5:])
    assert obs.registry.counter("train/compiles").value == sum(
        e["compiles"] for e in ev)


def test_fit_unregisters_compile_listener_on_error():
    class Broken:
        def __init__(self, data):
            self.data = data

        def batch(self, step):
            if step == 2:
                raise RuntimeError("input pipeline failed")
            return self.data.batch(step)

    mlp, params, data = _problem()
    ocfg = ObsConfig(enabled=True)
    obs = Obs(ocfg)
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=3.0, obs=ocfg),
                          family="bernoulli", obs=obs)
    tr = Trainer(mlp, opt, TrainConfig(steps=4, seed=0, log_every=10 ** 9,
                                       obs=ocfg), obs=obs)
    listeners = _duration_listeners()
    with pytest.raises(RuntimeError, match="input pipeline"):
        tr.fit(params, Broken(data), 4, log=lambda *_: None)
    assert _duration_listeners() == listeners


@pytest.mark.parametrize("kw", [
    {},
    {"inv_mode": "eigen", "refresh_mode": "staggered"},
    {"use_rescale": False},
], ids=["blkdiag", "eigen-staggered", "fixed-lr"])
def test_stage_programs_are_named(kw):
    """No stage program of the pipeline is a ``<lambda>``: each module
    reads ``jit_<name>`` in a trace or compile log."""
    mlp, params, data = _problem()
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=3.0, t3=3, **kw),
                          family="bernoulli")
    pipe = opt.update.__self__
    jitted = []
    for v in vars(pipe).values():
        for f in (v.values() if isinstance(v, dict) else [v]):
            if hasattr(f, "lower"):
                jitted.append(f)
    names = {f.__name__ for f in jitted}
    assert "<lambda>" not in names
    assert {"kfac_refresh", "kfac_update", "kfac_update3"} <= names
    if "refresh_mode" in kw:
        assert "kfac_refresh_group" in names
    state = opt.init(params, data.batch(0))
    assert "@jit_kfac_refresh" in pipe._refresh.lower(state).as_text()


# ---------------------------------------------------------------------------
# the disabled-parity pin: serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    from repro.configs import get_reduced_config
    from repro.models.lm import LM
    cfg = get_reduced_config("smollm-135m")
    lm = LM(cfg)
    return lm, lm.init_params(jax.random.PRNGKey(0)), cfg


def _serve_reqs(cfg):
    from repro.serving.server import Request
    return [Request(uid=u, prompt=[(5 * u + j) % cfg.vocab_size
                                   for j in range(3 + u % 3)], max_new=5)
            for u in range(5)]


def test_serving_disabled_bitwise_parity(smollm, tmp_path):
    from repro.serving.server import Engine
    lm, params, cfg = smollm
    outs, caches, reports = [], [], []
    for enabled in (False, True):
        obs = Obs(ObsConfig(enabled=enabled,
                            jsonl_path=(str(tmp_path / "serve.jsonl")
                                        if enabled else "")))
        eng = Engine(lm, params, batch_slots=2, max_len=24, page_size=4,
                     num_pages=8, obs=obs)
        reqs = _serve_reqs(cfg)
        reports.append(eng.run(reqs))
        obs.close()
        outs.append([r.out for r in reqs])
        caches.append(eng._step._cache_size())

    assert outs[0] == outs[1], "telemetry changed the served tokens"
    assert caches[0] == caches[1], "obs=enabled recompiled the decode step"
    off, on = reports
    assert (off.steps, len(off.completed)) == (on.steps, len(on.completed))
    assert off.preemptions == on.preemptions
    # latency aggregates exist only on the enabled run
    assert off.ttft_p50_ms is None and on.ttft_p50_ms > 0
    assert on.decode_p50_ms > 0

    events = read_jsonl(str(tmp_path / "serve.jsonl"))
    kinds = [e["event"] for e in events]
    assert kinds.count("serve_request") == len(on.completed)
    assert kinds[-1] == "serve_run"
    req_evs = [e for e in events if e["event"] == "serve_request"]
    assert all(e["n_tokens"] > 0 and e["ttft_ms"] > 0 for e in req_evs)


def test_engine_counters_feed_report(smollm):
    """RunReport preemption/eviction aggregates are per-run counter deltas
    — a warmup run on the same engine must not leak into them."""
    from repro.serving.server import Engine
    lm, params, cfg = smollm
    eng = Engine(lm, params, batch_slots=3, max_len=24, page_size=2,
                 num_pages=7)                # tiny pool -> eviction pressure
    first = eng.run(_serve_reqs(cfg))
    assert first.preemptions > 0 and first.evictions > 0
    c = eng.obs.registry.counter("serve/preemptions").value
    eng.reset()
    second = eng.run(_serve_reqs(cfg))
    # deltas, not lifetime totals:
    assert second.preemptions == first.preemptions
    assert eng.obs.registry.counter("serve/preemptions").value == 2 * c
    assert eng.obs.registry.counter(
        "serve/sampled", {"mode": "greedy"}).value > 0


# ---------------------------------------------------------------------------
# the latency split (shared with bench_serving)
# ---------------------------------------------------------------------------

def test_latency_tracker_split_and_percentiles():
    lat = RequestLatencyTracker()
    lat.on_submit(1, t=0.0)
    assert lat.on_emit(1, t=0.25) == ("ttft", 0.25)
    assert lat.on_emit(1, t=0.30) == ("decode", pytest.approx(0.05))
    lat.on_submit(2, t=0.1)
    assert lat.on_emit(2, t=0.5)[0] == "ttft"
    assert lat.on_emit(2, t=0.6)[0] == "decode"
    with pytest.raises(ValueError, match="before on_submit"):
        lat.on_emit(99)
    p = lat.percentiles()
    ttft_ms = [250.0, 400.0]
    dec_ms = [50.0, 100.0]
    assert p["ttft_p50_ms"] == pytest.approx(np.percentile(ttft_ms, 50))
    assert p["ttft_p99_ms"] == pytest.approx(np.percentile(ttft_ms, 99))
    assert p["decode_p50_ms"] == pytest.approx(np.percentile(dec_ms, 50))
    assert lat.n_tokens == 4
    lenient = RequestLatencyTracker()
    assert lenient.percentiles_or_none()["ttft_p50_ms"] is None


def test_latency_tracker_mirrors_registry():
    reg = Registry()
    lat = RequestLatencyTracker(reg)
    lat.on_submit(0, t=0.0)
    lat.on_emit(0, t=0.2)
    lat.on_emit(0, t=0.3)
    assert reg.histogram("serve/ttft_ms").snapshot()["count"] == 1
    assert reg.histogram("serve/decode_gap_ms").snapshot()["count"] == 1


# ---------------------------------------------------------------------------
# OverlapController telemetry: cancelled buffers counted, not dropped
# ---------------------------------------------------------------------------

def test_overlap_controller_counts_cancel_and_forced_commit():
    from repro.distributed.overlap import OverlapController

    class _Stuck:
        def is_ready(self):
            return False

    @dataclasses.dataclass(frozen=True)
    class MiniState:
        factors: object
        gamma: object
        inv: object
        inv_pending: object
        staleness: object

        def replace(self, **kw):
            return dataclasses.replace(self, **kw)

    obs = Obs()                              # disabled: counters still live
    ctl = OverlapController(lambda f, g, p: {"w": _Stuck()}, bound=3,
                            obs=obs)
    state = MiniState(factors={}, gamma=1.0, inv={"w": 0},
                      inv_pending={"w": 0}, staleness=jnp.int32(0))

    # dispatch at 3, cancel at 5 (T2 sweep): age 2 counted, not discarded
    state = ctl.on_refresh_stage(state, step=3, due=True)
    assert ctl.pending is not None
    ctl.cancel(step=5)
    assert ctl.pending is None
    assert ctl.n_cancelled == 1 and ctl.cancelled_age_steps == 2
    assert obs.registry.counter("overlap/cancelled_buffers").value == 1
    assert obs.registry.histogram(
        "overlap/cancelled_buffer_s").snapshot()["count"] == 1

    # dispatch at 6, never ready -> forced (blocking) commit at 9
    state = ctl.on_refresh_stage(state, step=6, due=True)
    state = ctl.on_refresh_stage(state, step=7, due=False)
    assert ctl.last_staleness == 1
    state = ctl.on_refresh_stage(state, step=8, due=False)
    state = ctl.on_refresh_stage(state, step=9, due=True)
    assert ctl.n_commits == 1 and ctl.n_forced_commits == 1
    assert ctl.last_forced and ctl.last_refresh_s > 0
    assert obs.registry.counter("overlap/forced_commits").value == 1
    assert ctl.last_staleness == 0


# ---------------------------------------------------------------------------
# thread safety: serving engine + daemon writers share one registry/sink
# ---------------------------------------------------------------------------

def test_registry_thread_safety():
    reg = Registry()
    c = reg.counter("n")
    h = reg.histogram("v")
    n_threads, n_iter = 8, 500

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(float(i))
            reg.counter("n")                 # concurrent get-or-create

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.snapshot()["count"] == n_threads * n_iter


def test_jsonl_sink_concurrent_writers(tmp_path):
    path = str(tmp_path / "conc.jsonl")
    sink = JsonlSink(path)
    n_threads, n_iter = 6, 200

    def work(tid):
        for i in range(n_iter):
            sink.write("custom", {"tid": tid, "i": i})

    ts = [threading.Thread(target=work, args=(t,))
          for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    sink.close()
    events = read_jsonl(path)                # every line parses + validates
    assert len(events) == n_threads * n_iter


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_obs_config_defaults_disabled():
    assert KFACConfig().obs.enabled is False
    assert TrainConfig().obs.enabled is False
    o = Obs()
    assert not o.enabled and o.sink is None
    # disabled span is the shared no-op (no allocation per call)
    s1, s2 = o.span("a"), o.span("b")
    assert s1 is s2
    with s1:
        pass
    assert s1.seconds is None
