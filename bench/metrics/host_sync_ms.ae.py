"""``host_sync_ms`` for the autoencoder cell, whose step metric is its own
(``train_step_ms.ae``): the same reader."""
from bench.lib import harness as H

read = H.load_module("metrics", "host_sync_ms").read
