"""``lm_tokens``: ``n_batches`` batches of ``batch`` × ``seq`` tokens whose
unigram law is Zipf's (exponent ``zipf``) over a seed-drawn ranking of the
vocabulary; labels are the next tokens.  Cycled by step."""
import jax
import jax.numpy as jnp


class LMTokens:

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


def make(spec: dict, cfg: dict, key):
    return LMTokens(spec, cfg["vocab_size"], key)
