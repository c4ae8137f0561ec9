"""repro.obs — unified telemetry: metrics registry, spans, exporters.

One lightweight subsystem observes all three planes (training,
distributed refresh, serving):

* :class:`~repro.obs.metrics.Registry` — typed ``Counter`` / ``Gauge`` /
  ``Histogram`` instruments with labels, thread-safe, exact p50/p99 over
  a bounded reservoir;
* :mod:`~repro.obs.tracing` — trace-safe spans (device work timed
  host-side after ``block_until_ready`` at span close, never via
  callbacks inside jit; optional ``jax.profiler.TraceAnnotation``
  pass-through) and the compile listener :class:`CompileClock`;
* :mod:`~repro.obs.export` — append-only schema-versioned JSONL event
  sink, console summarizer (the one formatting path the launchers
  render from);
* :mod:`~repro.obs.latency` — the shared TTFT / decode-gap definitions
  (live engine telemetry and ``bench_serving`` use the same class).

Everything rides behind :class:`ObsConfig` (threaded through
``TrainConfig`` / ``KFACConfig`` / the serving-engine constructor).  The
facade is :class:`Obs`: counters/gauges always count (plain host
integers — they feed ``RunReport``-style summaries even when disabled),
while *timing* (spans, sync points), the JSONL sink and the console
summary exist only when ``enabled=True`` — the disabled program is
bitwise-identical to an uninstrumented one, with the same jitted
functions and no extra host syncs (pinned by ``tests/test_obs.py``).
See ``docs/observability.md`` for the metric catalog.
"""
from __future__ import annotations

from typing import Callable, Optional, Union

from repro.obs.config import ObsConfig
from repro.obs.export import (JsonlSink, console_summary, read_jsonl,
                              validate_event, SCHEMA_VERSION)
from repro.obs.latency import RequestLatencyTracker
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               percentile)
from repro.obs.tracing import NULL_SPAN, CompileClock, NullSpan, Span

__all__ = [
    "Obs", "ObsConfig", "from_config",
    "Counter", "Gauge", "Histogram", "Registry", "percentile",
    "Span", "NullSpan", "NULL_SPAN", "CompileClock",
    "JsonlSink", "console_summary", "read_jsonl",
    "validate_event", "SCHEMA_VERSION",
    "RequestLatencyTracker",
]


class Obs:
    """Facade: one registry + (when enabled) one JSONL sink + console.

    Share a single ``Obs`` across planes (trainer, optimizer pipeline,
    serving engine) to land their events in one log file; the launchers
    do exactly that."""

    def __init__(self, config: Optional[ObsConfig] = None,
                 registry: Optional[Registry] = None):
        self.config = config if config is not None else ObsConfig()
        self.registry = registry if registry is not None else Registry(
            self.config.reservoir)
        self.sink = (JsonlSink(self.config.jsonl_path)
                     if self.config.enabled and self.config.jsonl_path
                     else None)

    @property
    def enabled(self) -> bool:
        return self.config.enabled

    # -- instruments (always live: cheap host counters) ----------------
    def counter(self, name: str, labels=None) -> Counter:
        return self.registry.counter(name, labels)

    def gauge(self, name: str, labels=None) -> Gauge:
        return self.registry.gauge(name, labels)

    def histogram(self, name: str, labels=None) -> Histogram:
        return self.registry.histogram(name, labels)

    # -- timing (enabled only) -----------------------------------------
    def span(self, name: str,
             block: Union[None, Callable, object] = None
             ) -> Union[Span, NullSpan]:
        """Trace-safe span: wall seconds recorded into the
        ``span_s{span=<name>}`` histogram at close, after blocking on
        ``block``.  The disabled path is a shared no-op context manager
        (no clock reads, no blocking)."""
        if not self.enabled:
            return NULL_SPAN
        return Span(name, self.registry.histogram("span_s", {"span": name}),
                    block=block, annotate=self.config.trace_annotations)

    # -- events (enabled only) -----------------------------------------
    def emit(self, event: str, **payload) -> None:
        if self.sink is not None:
            self.sink.write(event, payload)

    def maybe_console(self, step: int, title: str = "obs") -> None:
        every = self.config.console_every
        if self.enabled and every > 0 and step % every == 0:
            print(self.summary(title))

    def summary(self, title: str = "obs") -> str:
        return console_summary(self.registry, title)

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()


def from_config(cfg: Union[None, ObsConfig, Obs]) -> Obs:
    """Coerce an ObsConfig (or None, or an existing Obs) into an Obs."""
    if isinstance(cfg, Obs):
        return cfg
    return Obs(cfg)
