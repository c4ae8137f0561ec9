"""The training loop's readers (``host_sync_ms``, ``idle_loop_share``) on
small hand-made traces in the form of ``test_bench_readers.ctx``, with
values worked out by hand.  Times are in milliseconds × 1e6 (ns)."""
import pytest

from bench.lib import harness as H
from bench.tests.test_bench_readers import ctx

MS = 1e6
SYNC = ("host_sync_ms", "host_sync_ms.ae")
IDLE = ("idle_loop_share.train", "idle_loop_share.ae")
# device busy on [0, 100), [300, 500), [800, 1000): idle on [100, 300)
# and [500, 800) of the window [0, 1000)
OPS = [("%fusion.1", 0, 100 * MS), ("%fusion.2", 300 * MS, 500 * MS),
       ("%fusion.3", 800 * MS, 1000 * MS)]
HOST = [
    ("train/step_inputs", 50 * MS, 150 * MS),      # 50 idle
    ("train/step", 150 * MS, 400 * MS),            # not a sync site
    ("kfac/read_step", 200 * MS, 250 * MS),        # 50 idle, inside a step
    ("kfac/adapt_lambda", 250 * MS, 300 * MS),     # not a sync site
    ("train/finite_check", 400 * MS, 600 * MS),    # 100 idle
    ("train/metrics_to_host", 650 * MS, 700 * MS),  # 50 idle
    ("train/emit", 700 * MS, 800 * MS),            # idle, not charged
    ("kfac/adapt_lambda", 880 * MS, 960 * MS),
    ("kfac/lambda_guard", 900 * MS, 950 * MS),     # device busy: 0 idle
]


def window(**kw):
    return ctx(OPS, HOST, lo=0, hi=1000 * MS, steps=2, **kw)


@pytest.mark.parametrize("name", SYNC)
def test_host_sync_ms_sums_the_sync_sites_per_step(name):
    # read_step 50 + finite_check 200 + metrics_to_host 50 + lambda_guard
    # 50 = 350 ms over 2 steps; step inputs, stages and emit not counted
    assert H.load_module("metrics", name).read(window()) == \
        pytest.approx(175.0)


@pytest.mark.parametrize("name", IDLE)
def test_idle_loop_share_charges_only_idle_under_the_sites(name):
    # 50 + 50 + 100 + 50 = 250 ms of the 500 idle ms, over 1000 ms: the
    # idle under train/step, kfac/adapt_lambda and train/emit is not
    # charged, nor the sites' time while the device is busy
    assert H.load_module("metrics", name).read(window()) == \
        pytest.approx(25.0)


def test_readers_clip_to_the_window():
    c = window()
    c.lo, c.hi = 120 * MS, 620 * MS
    # sync sites: read_step 50 + finite_check 200 → 125 per step
    assert H.load_module("metrics", "host_sync_ms").read(c) == \
        pytest.approx(125.0)
    # idle [120, 300) and [500, 620): step_inputs 30, read_step 50,
    # finite_check 100 → 180 of 500
    assert H.load_module("metrics", "idle_loop_share.train").read(c) == \
        pytest.approx(36.0)


def test_idle_loop_share_averages_over_devices():
    c = window()
    # a second device busy all window long: nothing idle to charge there
    c.trace["devices"]["/device:TPU:1"] = [("%fusion.9", 0, 1000 * MS)]
    assert H.load_module("metrics", "idle_loop_share.train").read(c) == \
        pytest.approx(12.5)


def test_nested_and_overlapping_site_spans_count_once_as_idle():
    host = [("train/finite_check", 100 * MS, 300 * MS),
            ("kfac/lambda_guard", 150 * MS, 250 * MS)]
    c = ctx(OPS, host, lo=0, hi=1000 * MS, steps=1)
    assert H.load_module("metrics", "idle_loop_share.train").read(c) == \
        pytest.approx(20.0)
    # the time spent at each site is summed
    assert H.load_module("metrics", "host_sync_ms").read(c) == \
        pytest.approx(300.0)


@pytest.mark.parametrize("name", SYNC + IDLE)
def test_readers_return_nothing_without_the_spans(name):
    """A program without the loop's spans (the parent's case): nothing,
    not zero, and no error — also when the spans lie outside the window."""
    reader = H.load_module("metrics", name)
    others = [h for h in HOST if h[0] in ("train/step", "kfac/adapt_lambda",
                                          "train/emit")]
    assert reader.read(ctx(OPS, others, lo=0, hi=1000 * MS,
                           steps=2)) is None
    late = [(n, s + 2000 * MS, e + 2000 * MS) for n, s, e in HOST]
    assert reader.read(ctx(OPS, late, lo=0, hi=1000 * MS, steps=2)) is None
