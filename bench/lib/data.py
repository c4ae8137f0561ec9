"""Training inputs made from the seed on the device, by kind.

A traffic file's ``data`` entry names a kind and its parameters;
``bench/inputs/<kind>.py`` makes the batches, in one jitted call during
set-up, and they stay on the device.  ``batch(step)`` of what its
``make(spec, cfg, key)`` returns is what the trainer calls each step.
On a mesh, each of the ``n_batches`` batches is put once, during set-up,
with its rows sharded over the mesh's ``data`` axis.
"""
from __future__ import annotations

from bench.lib import harness as H


class OnMesh:
    """A kind's ``n`` batches, each put on ``mesh`` with its rows sharded
    over ``data``; cycled by step like the kind's own."""

    def __init__(self, data, n: int, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        rows = NamedSharding(mesh, P("data"))
        self.batches = [jax.device_put(data.batch(k), rows)
                        for k in range(n)]

    def batch(self, step: int):
        return self.batches[step % len(self.batches)]


def make(spec: dict, cfg: dict, key, bench: str = H.BENCH, mesh=None):
    data = H.load_module("inputs", spec["kind"], bench).make(spec, cfg, key)
    return data if mesh is None else OnMesh(data, spec["n_batches"], mesh)
