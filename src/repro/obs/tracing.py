"""Trace-safe span timing for JAX programs.

The one rule: device work is timed **host-side**, by blocking on the
span's declared outputs at span *close* (``jax.block_until_ready``) —
never via callbacks inside a jitted function.  A span therefore measures
dispatch + device execution of whatever pytree you hand it, and the
jitted program itself is untouched (spans never appear in the HLO, so
disabled-vs-enabled programs are identical; only the host's sync points
differ).

    with obs.span("refresh/eigh", block=lambda: state.inv):
        state = refresh(state)

``block`` may be a pytree or a zero-arg callable evaluated at exit (use
the callable form when the arrays are produced inside the ``with``
body).  With ``ObsConfig.trace_annotations`` the span also enters a
``jax.profiler.TraceAnnotation``, so the same names line up in
TensorBoard / perfetto device profiles.  A span with no ``block`` adds
no sync: it times whatever sync its body already makes.

:class:`CompileClock` is the program's one listener for JAX's compile
events: seconds spent tracing, lowering and compiling, and the count of
backend compiles, inside its ``with`` block.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Union

from repro.obs.metrics import Histogram


class Span:
    """Context manager: wall seconds from enter to (blocked) exit,
    recorded into ``hist`` and readable as ``.seconds`` afterwards."""

    def __init__(self, name: str, hist: Optional[Histogram] = None,
                 block: Union[None, Callable, object] = None,
                 annotate: bool = False):
        self.name = name
        self.hist = hist
        self.block = block
        self.seconds: Optional[float] = None
        self._annotation = None
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self.block is not None:
            import jax
            tree = self.block() if callable(self.block) else self.block
            if tree is not None:
                jax.block_until_ready(tree)
        self.seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None and self.hist is not None:
            self.hist.observe(self.seconds)
        return False


class NullSpan:
    """The disabled path: no clock reads, no blocking, no recording."""

    name = ""
    seconds = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = NullSpan()


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling inside the
    ``with`` block, and the backend compiles among them, from its own
    monitoring events.  The listener is registered on enter and always
    removed on exit, an exception included."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.COMPILE:
            self.compiles += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False
