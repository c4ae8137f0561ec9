"""``binary_lowrank``: ``examples`` binary vectors of width ``dim`` (the
encoder's first) drawn from a ``latent``-dimensional logistic model (the
autoencoder's stand-in for MNIST), cut into ``examples / batch``
minibatches that are cycled by step."""
import jax
import jax.numpy as jnp


class BinaryLowRank:

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
    return BinaryLowRank(spec, cfg["encoder"][0], key)
