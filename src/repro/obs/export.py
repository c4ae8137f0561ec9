"""Exporters: JSONL event sink and console summary.

JSONL event schema (version ``SCHEMA_VERSION``)
-----------------------------------------------
One JSON object per line.  Every event carries::

    {"v": 1, "event": "<type>", "ts": <unix seconds>, ...}

Known event types and their required fields (``EVENT_FIELDS``):

* ``train_step``    — ``step``, ``loss``, ``wall_s`` (+ lam/gamma/alpha/
  rho/nu/staleness/rejected/fused_stats when applicable, and the step's
  host_syncs and compiles)
* ``kfac_step``     — ``step``, ``stages`` ({stage name: seconds})
* ``refresh``       — ``mode``, ``wall_s`` (+ plan cost / shard info /
  forced / cancelled for the distributed modes)
* ``serve_request`` — ``uid``, ``n_tokens`` (+ ttft_ms / decode gap
  stats / preemptions)
* ``serve_run``     — ``steps`` (+ completed / preemptions / evictions /
  latency percentiles)

Unknown event types are allowed (forward compatibility) but must still
carry ``v``/``event``/``ts`` and only finite numbers.
``benchmarks/obs_check.py`` is the CI gate over a written log file;
``validate_event`` here is the single source of truth it calls.

The sink appends each line with one ``os.write`` on an ``O_APPEND`` fd,
so concurrent writers (trainer thread + controller daemon, or two ``Obs``
instances pointed at one path) never interleave partial lines.
"""
from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, Optional

from repro.obs.metrics import Registry

SCHEMA_VERSION = 1

EVENT_FIELDS: Dict[str, tuple] = {
    "train_step": ("step", "loss", "wall_s"),
    "kfac_step": ("step", "stages"),
    "refresh": ("mode", "wall_s"),
    "serve_request": ("uid", "n_tokens"),
    "serve_run": ("steps",),
}


def _check_finite(obj, path: str) -> None:
    if isinstance(obj, bool) or obj is None or isinstance(obj, (str, int)):
        return
    if isinstance(obj, float):
        if not math.isfinite(obj):
            raise ValueError(f"non-finite number at {path}: {obj!r}")
        return
    if isinstance(obj, dict):
        for k, v in obj.items():
            _check_finite(v, f"{path}.{k}")
        return
    if isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            _check_finite(v, f"{path}[{i}]")
        return
    raise ValueError(f"unserializable value at {path}: {type(obj).__name__}")


def validate_event(obj) -> dict:
    """Raise ValueError unless ``obj`` is a schema-valid event dict."""
    if not isinstance(obj, dict):
        raise ValueError(f"event is {type(obj).__name__}, not dict")
    if obj.get("v") != SCHEMA_VERSION:
        raise ValueError(f"event schema v={obj.get('v')!r}, "
                         f"expected {SCHEMA_VERSION}")
    ev = obj.get("event")
    if not isinstance(ev, str) or not ev:
        raise ValueError("event has no 'event' type string")
    ts = obj.get("ts")
    if not isinstance(ts, (int, float)) or not math.isfinite(ts):
        raise ValueError(f"event {ev!r}: ts={ts!r} is not a finite time")
    for field in EVENT_FIELDS.get(ev, ()):
        if field not in obj:
            raise ValueError(f"event {ev!r} missing required field "
                             f"{field!r}")
    _check_finite(obj, ev)
    return obj


class JsonlSink:
    """Append-only JSONL writer (atomic whole-line appends)."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                           0o644)
        self._lock = threading.Lock()

    def write(self, event: str, payload: dict) -> dict:
        obj = {"v": SCHEMA_VERSION, "event": event,
               "ts": time.time(), **payload}
        line = json.dumps(obj, sort_keys=False, allow_nan=False) + "\n"
        with self._lock:
            if self._fd is not None:
                os.write(self._fd, line.encode())
        return obj

    def close(self) -> None:
        with self._lock:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None


def read_jsonl(path: str) -> list:
    """Parse + validate every event in a JSONL log; raises on any bad
    line (with its line number)."""
    out = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(validate_event(json.loads(line)))
            except ValueError as e:
                raise ValueError(f"{path}:{i}: {e}") from e
    return out


# ---------------------------------------------------------------------------
# Console summary — the ONE formatting path both launchers render from
# ---------------------------------------------------------------------------

def console_summary(registry: Registry, title: str = "obs") -> str:
    """Human-readable snapshot: counters, gauges, then histogram stats.
    ``launch/train.py`` and ``launch/serve.py`` print exactly this — the
    ad-hoc per-launcher stat lines are gone."""
    snap = registry.snapshot()
    lines = [f"[{title}] --- telemetry snapshot ---"]
    for key, val in snap["counter"].items():
        lines.append(f"[{title}] {key} = {val:g}")
    for key, val in snap["gauge"].items():
        lines.append(f"[{title}] {key} = {val:g}")
    for key, st in snap["histogram"].items():
        if st["count"] == 0:
            continue
        lines.append(
            f"[{title}] {key}: n={st['count']} mean={st['mean']:.4g}"
            + (f" p50={st['p50']:.4g} p99={st['p99']:.4g}"
               f" max={st['max']:.4g}" if "p50" in st else ""))
    return "\n".join(lines)
