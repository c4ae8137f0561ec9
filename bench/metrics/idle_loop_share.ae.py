"""``idle_loop_share.train`` for the autoencoder cell, whose step metric is
its own (``train_step_ms.ae``): the same reader."""
from bench.lib import harness as H

read = H.load_module("metrics", "idle_loop_share.train").read
