"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
tuples; everything after that is pure Python over ``(name, start_ns,
end_ns)`` intervals, so it is checked on small hand-made traces
(``bench/tests/test_trace.py``).

* busy time: the union of the intervals in which an operation runs on a
  device, inside the window, averaged over the devices used;
* idle gaps: the stretches of the window in which a device runs nothing,
  each named after the innermost host span (a ``TraceAnnotation``) that
  covers its middle, or ``"(no span)"``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]       # (name, start_ns, end_ns)


def load(log_dir: str) -> dict:
    """{"devices": {plane: [ops]}, "host": [spans]} from the newest trace
    under ``log_dir``.  Device ops come from each TPU plane's ``XLA Ops``
    line; host spans from every thread line of the host plane."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals: List[Interval]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) covering every interval."""
    merged: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: List[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals: List[Interval], lo: float, hi: float):
    """Idle (start, end) stretches of [lo, hi] between busy intervals."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covering_spans(host: List[Interval], ts) -> List[str]:
    """For each time in ``ts``, the innermost (shortest) host span that
    contains it, or ``"(no span)"``."""
    import numpy as np
    if not host:
        return ["(no span)"] * len(ts)
    s = np.array([h[1] for h in host], float)
    e = np.array([h[2] for h in host], float)
    out = []
    for t in ts:
        dur = np.where((s <= t) & (t <= e), e - s, np.inf)
        i = int(np.argmin(dur))
        out.append(host[i][0] if np.isfinite(dur[i]) else "(no span)")
    return out


def short(name: str, width: int = 120) -> str:
    """An op's HLO text cut to its name and the start of its shape."""
    return name if len(name) <= width else name[: width - 3] + "..."


def window(host: List[Interval], name: str):
    """(start, end) of the host span called ``name`` (the last one)."""
    found = [(s, e) for n, s, e in host if n == name]
    if not found:
        raise KeyError(f"no host span {name!r} in the trace")
    return found[-1]


def reduce(tr: dict, lo: float, hi: float, top: int = 10) -> dict:
    """Busy seconds (mean over devices), window seconds, and the
    breakdown the result line carries: the device ops that took most
    time, and the longest idle gaps named by the host span over them."""
    devs = tr["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = sum(busy_ns(ops, lo, hi) for ops in devs.values()) / len(devs)
    by_name: Dict[str, float] = {}
    first = next(iter(sorted(devs)))
    for name, s, e in clip(devs[first], lo, hi):
        by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9
    idle: Dict[str, float] = {}
    host = clip(tr["host"], lo, hi)
    longest = sorted(gaps(devs[first], lo, hi), key=lambda g: g[0] - g[1])
    longest = longest[:200]
    names = covering_spans(host, [0.5 * (s + e) for s, e in longest])
    for who, (s, e) in zip(names, longest):
        idle[who] = idle.get(who, 0.0) + (e - s) * 1e-9
    ops_top = sorted(by_name.items(), key=lambda x: -x[1])[:top]
    idle_top = sorted(idle.items(), key=lambda x: -x[1])[:top]
    return {"busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9,
            "device_ops": [[short(n), v] for n, v in ops_top],
            "idle_gaps": [[n, v] for n, v in idle_top]}
