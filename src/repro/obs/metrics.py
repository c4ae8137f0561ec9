"""Typed metrics registry: Counter / Gauge / Histogram with labels.

Thread-safe by construction — the serving engine, the ``BundleWriter``
and the ``OverlapController`` all touch metrics from daemon threads, so
every mutation takes the owning registry's lock.  The primitives are
deliberately dumb host-side objects: never called from inside a jitted
function (tracing discipline lives in ``obs.tracing``).

Histogram percentiles are *exact* over a bounded reservoir: the most
recent ``bound`` observations are kept verbatim (a sliding window, not a
sampling sketch) and ``percentile`` reproduces ``numpy.percentile``'s
default linear interpolation over that window bit-for-bit — pinned
against the numpy reference in ``tests/test_obs.py``.
"""
from __future__ import annotations

import math
import threading
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Optional[Dict[str, str]]) -> LabelKey:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def percentile(samples: Iterable[float], q: float) -> float:
    """``numpy.percentile(..., method="linear")`` without numpy: sorted
    rank ``q/100 * (n-1)``, linearly interpolated between neighbors."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of empty sample set")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q={q} outside [0, 100]")
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if lo == hi:
        return xs[int(rank)]
    frac = rank - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


class Metric:
    """Base: a named instrument bound to one label set in one registry."""

    kind = "untyped"

    def __init__(self, name: str, labels: LabelKey, lock: threading.Lock):
        self.name = name
        self.labels = labels
        self._lock = lock


class Counter(Metric):
    """Monotonic count (events, tokens, rejected steps, ...)."""

    kind = "counter"

    def __init__(self, name, labels, lock):
        super().__init__(name, labels, lock)
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: inc({n}) < 0")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge(Metric):
    """Last-write-wins level (queue depth, staleness, lambda, ...)."""

    kind = "gauge"

    def __init__(self, name, labels, lock):
        super().__init__(name, labels, lock)
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram(Metric):
    """Count/sum/min/max plus a bounded reservoir of the most recent
    ``bound`` observations; ``percentile`` is exact over the window."""

    kind = "histogram"

    def __init__(self, name, labels, lock, bound: int = 2048):
        super().__init__(name, labels, lock)
        self.bound = max(1, int(bound))
        self._window: deque = deque(maxlen=self.bound)
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self._window.append(v)
            self._count += 1
            self._sum += v
            self._min = min(self._min, v)
            self._max = max(self._max, v)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    @property
    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def percentile(self, q: float) -> float:
        with self._lock:
            return percentile(self._window, q)

    def snapshot(self) -> dict:
        with self._lock:
            window = list(self._window)
        out = {"count": self._count, "sum": self._sum,
               "mean": (self._sum / self._count if self._count else 0.0)}
        if window:
            out["min"] = self._min
            out["max"] = self._max
            out["p50"] = percentile(window, 50)
            out["p99"] = percentile(window, 99)
        return out


class Registry:
    """Get-or-create instrument store keyed by (name, labels).

    One lock guards both the instrument table and every instrument's
    mutations — contention is negligible at telemetry rates and the
    single lock keeps snapshot consistency trivial."""

    def __init__(self, reservoir: int = 2048):
        self.reservoir = reservoir
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, LabelKey], Metric] = {}

    def _get(self, cls, name: str, labels, **kw) -> Metric:
        # keyed by (name, labels) — one name maps to ONE kind; asking for
        # the same name as a different kind is a bug, not a new instrument
        key = (name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, key[1], self._lock, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{m.kind}, not {cls.kind}")
        return m

    def counter(self, name: str, labels: Optional[Dict[str, str]] = None
                ) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, labels: Optional[Dict[str, str]] = None
              ) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, labels: Optional[Dict[str, str]] = None,
                  bound: Optional[int] = None) -> Histogram:
        return self._get(Histogram, name, labels,
                         bound=bound or self.reservoir)

    # ------------------------------------------------------------------
    def metrics(self) -> List[Metric]:
        with self._lock:
            return [self._metrics[k] for k in sorted(self._metrics)]

    def find(self, name: str, kind: Optional[str] = None) -> List[Metric]:
        """Every instrument registered under ``name`` (any label set)."""
        return [m for m in self.metrics()
                if m.name == name and (kind is None or m.kind == kind)]

    def snapshot(self) -> dict:
        """Plain-data view: {kind: {name{labels}: value-or-stats}}."""
        out: Dict[str, dict] = {"counter": {}, "gauge": {}, "histogram": {}}
        for m in self.metrics():
            label_s = ",".join(f"{k}={v}" for k, v in m.labels)
            key = f"{m.name}{{{label_s}}}" if label_s else m.name
            if isinstance(m, Histogram):
                out["histogram"][key] = m.snapshot()
            else:
                out[m.kind][key] = m.value
        return out
