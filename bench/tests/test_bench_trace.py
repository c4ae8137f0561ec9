"""The trace reduction on small hand-made traces."""
import pytest

from bench.lib import trace as T


def test_union_merges_overlaps_and_sorts():
    ops = [("b", 5, 8), ("a", 0, 3), ("c", 2, 4), ("d", 8, 9)]
    assert T.union(ops) == [(0, 4), (5, 9)]


def test_busy_is_union_clipped_to_the_window():
    ops = [("x", 0, 10), ("y", 5, 15), ("z", 30, 40)]
    assert T.busy_ns(ops, 2, 35) == 13 + 5


def test_gaps_cover_the_rest_of_the_window():
    ops = [("x", 10, 20), ("y", 30, 40)]
    assert T.gaps(ops, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    busy = T.busy_ns(ops, 0, 50)
    assert busy + sum(e - s for s, e in T.gaps(ops, 0, 50)) == 50


def test_gap_named_after_innermost_covering_span():
    host = [("outer", 0, 100), ("inner", 20, 40)]
    assert T.covering_spans(host, [30, 60, 200]) == ["inner", "outer",
                                                      "(no span)"]


def test_reduce_idle_share_and_breakdown():
    tr = {"devices": {"/device:TPU:0": [("mm", 0, 6e8), ("mm", 7e8, 9e8),
                                        ("add", 9e8, 9.5e8)]},
          "host": [("bench/window", 0, 1e9), ("sync", 6e8, 7e8)]}
    red = T.reduce(tr, 0, 1e9)
    assert red["window_s"] == pytest.approx(1.0)
    assert red["busy_s"] == pytest.approx(0.85)
    assert red["device_ops"][0] == ["mm", pytest.approx(0.8)]
    names = dict((n, v) for n, v in red["idle_gaps"])
    assert names["sync"] == pytest.approx(0.1)
    assert names["bench/window"] == pytest.approx(0.05)


def test_reduce_averages_busy_over_devices():
    tr = {"devices": {"/device:TPU:0": [("a", 0, 10)],
                      "/device:TPU:1": [("a", 0, 20)]}, "host": []}
    assert T.reduce(tr, 0, 40)["busy_s"] == pytest.approx(15e-9)


def test_window_takes_the_named_span():
    host = [("bench/window", 5, 50), ("other", 0, 100)]
    assert T.window(host, "bench/window") == (5, 50)
    with pytest.raises(KeyError):
        T.window(host, "missing")
