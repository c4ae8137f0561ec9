"""Where the Pallas kernels run, and which shapes they take.

Nothing here reads the backend when it is imported: ``resolve_interpret``
asks ``jax.default_backend()`` at call (trace) time, so importing a model
never initialises a device, and a kernel called on a TPU is compiled
unless its caller asks for the interpreter by name.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether the default backend is a TPU (asked now, not at import)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> compiled on a TPU, the interpreter elsewhere."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def tile_ok(*dims: int) -> bool:
    """Whether every dim tiles cleanly into the kernels' 128-blocks.

    min(128, d) is used as the block size, so d <= 128 needs only MXU lane
    alignment (d % 8); larger dims must be whole multiples of 128.
    """
    return all(d % 128 == 0 or (0 < d <= 128 and d % 8 == 0) for d in dims)
