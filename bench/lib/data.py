"""Training inputs made from the seed on the device, by kind.

A traffic file's ``data`` entry names a kind and its parameters; the
batches are made in one jitted call during set-up and stay on the
device.  ``batch(step)`` is what the trainer calls each step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class LMTokens:
    """``n_batches`` batches of ``batch`` × ``seq`` tokens whose unigram
    law is Zipf's (exponent ``zipf``) over a seed-drawn ranking of the
    vocabulary; labels are the next tokens.  Cycled by step."""

    def __init__(self, spec: dict, vocab: int, key):
        nb, b, t = spec["n_batches"], spec["batch"], spec["seq"]

        @jax.jit
        def make(key):
            k1, k2 = jax.random.split(key)
            w = (jnp.arange(vocab, dtype=jnp.float32) + 1.0) ** -spec["zipf"]
            cdf = jnp.cumsum(w / jnp.sum(w))
            u = jax.random.uniform(k1, (nb, b, t + 1))
            rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
            ids = jax.random.permutation(k2, vocab)[rank].astype(jnp.int32)
            return ids[..., :-1], ids[..., 1:]

        tokens, labels = make(key)
        self.batches = [{"tokens": tokens[i], "labels": labels[i]}
                        for i in range(nb)]

    def batch(self, step: int):
        return self.batches[step % len(self.batches)]


class BinaryLowRank:
    """``examples`` binary vectors of width ``dim`` drawn from a
    ``latent``-dimensional logistic model (the autoencoder's stand-in for
    MNIST), cut into ``examples / batch`` minibatches that are cycled by
    step."""

    def __init__(self, spec: dict, dim: int, key):
        n, lat, bs = spec["examples"], spec["latent"], spec["batch"]
        if n % bs:
            raise ValueError(f"{n} examples do not cut into batches of {bs}")

        @jax.jit
        def make(key):
            k1, k2, k3 = jax.random.split(key, 3)
            z = jax.random.normal(k1, (n, lat))
            w = jax.random.normal(k2, (lat, dim)) * spec["scale"]
            p = jax.nn.sigmoid(z @ w)
            x = (jax.random.uniform(k3, (n, dim)) < p).astype(jnp.float32)
            return x.reshape(n // bs, bs, dim)

        x = make(key)
        self.batches = [{"x": x[i], "y": x[i]} for i in range(n // bs)]

    def batch(self, step: int):
        return self.batches[step % len(self.batches)]


def make(spec: dict, cfg: dict, key):
    if spec["kind"] == "lm_tokens":
        return LMTokens(spec, cfg["vocab_size"], key)
    if spec["kind"] == "binary_lowrank":
        return BinaryLowRank(spec, cfg["encoder"][0], key)
    raise ValueError(f"unknown data kind {spec['kind']!r}")
