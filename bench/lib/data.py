"""Training inputs made from the seed on the device, by kind.

A traffic file's ``data`` entry names a kind and its parameters;
``bench/inputs/<kind>.py`` makes the batches, in one jitted call during
set-up, and they stay on the device.  ``batch(step)`` of what its
``make(spec, cfg, key)`` returns is what the trainer calls each step.
"""
from __future__ import annotations

from bench.lib import harness as H


def make(spec: dict, cfg: dict, key, bench: str = H.BENCH):
    return H.load_module("inputs", spec["kind"], bench).make(spec, cfg, key)
