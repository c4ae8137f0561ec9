"""Plain reference of the deep autoencoder of Hinton & Salakhutdinov (2006),
the benchmark of the K-FAC paper (Martens & Grosse 2015, section 13).

Layers ``dims[i] -> dims[i+1]`` with the encoder widths mirrored into a
decoder; every map is affine (the bias is the last row of its weight,
applied to a homogeneous coordinate), every hidden unit is ``tanh``, and
the loss is the Bernoulli cross-entropy of the logits against the input.
It imports nothing of the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(cfg):
    enc = list(cfg["encoder"])
    return enc + enc[-2::-1]


def param_shapes(cfg):
    d = dims(cfg)
    return {f"W{i}": (d[i] + 1, d[i + 1]) for i in range(len(d) - 1)}


def make_params(cfg, key, dtype=jnp.float32):
    """The paper's sparse initialisation (Martens 2010) in one jitted call:
    each unit draws ``cfg["init_nonzero"]`` incoming weights from N(0, 1),
    the rest and the biases are zero."""
    d = dims(cfg)
    nz = cfg["init_nonzero"]

    @jax.jit
    def make(key):
        out = {}
        for i, k in enumerate(jax.random.split(key, len(d) - 1)):
            k1, k2 = jax.random.split(k)
            w = jax.random.normal(k1, (d[i], d[i + 1]))
            keep = jax.vmap(lambda kk: jax.random.permutation(kk, d[i]) < nz)(
                jax.random.split(k2, d[i + 1])).T
            w = jnp.where(keep, w, 0.0)
            out[f"W{i}"] = jnp.concatenate(
                [w, jnp.zeros((1, d[i + 1]))], axis=0).astype(dtype)
        return out

    return make(key)


def _with_one(a):
    return jnp.concatenate([a, jnp.ones(a.shape[:-1] + (1,), a.dtype)], -1)


def logits(params, x, probes=None, dtype=jnp.float32):
    n = len(params)
    a = x.astype(dtype)
    recs = {}
    for i in range(n):
        ab = _with_one(a)
        recs[f"layer{i}"] = ab
        s = ab @ params[f"W{i}"].astype(dtype)
        if probes is not None:
            s = s + probes[f"layer{i}"].astype(dtype)
        a = s if i == n - 1 else jnp.tanh(s)
    return a, recs


def nll(z, y):
    z = z.astype(jnp.float32)
    return jnp.sum(jnp.logaddexp(0.0, z) - y * z, axis=-1)


class Model:
    """What the K-FAC reference asks of a model, for the autoencoder: one
    dense Kronecker pair per layer, with the homogeneous coordinate on the
    A side; nothing untagged."""

    def __init__(self, cfg, dtype=jnp.float32):
        self.cfg = cfg
        self.dtype = dtype
        d = dims(cfg)
        self.blocks = {f"layer{i}": dict(path=(f"W{i}",), a_kind="full",
                                         g_kind="full", a_dim=d[i] + 1,
                                         g_dim=d[i + 1])
                       for i in range(len(d) - 1)}
        self._lgs = jax.jit(self._loss_grad_stats)
        self._loss = jax.jit(lambda p, x, y: jnp.mean(
            nll(logits(p, x, dtype=self.dtype)[0], y)))
        self._quad = jax.jit(self._fisher_quad)

    def _loss_grad_stats(self, params, batch, key):
        x, y = batch["x"], batch["y"]
        n = x.shape[0]

        def loss(p):
            z, _ = logits(p, x, dtype=self.dtype)
            return jnp.mean(nll(z, y))

        lt, grads = jax.value_and_grad(loss)(params)
        z, recs = logits(params, x, dtype=self.dtype)
        ys = jax.random.bernoulli(
            key, jax.nn.sigmoid(jax.lax.stop_gradient(z).astype(jnp.float32))
        ).astype(jnp.float32)
        probes = {k: jnp.zeros(z.shape[:1] + (b["g_dim"],), self.dtype)
                  for k, b in self.blocks.items()}
        cots = jax.grad(lambda pr: jnp.mean(nll(
            logits(params, x, pr, self.dtype)[0], ys)))(probes)
        ein = lambda u: jnp.einsum("ni,nj->ij", u, u,
                                   preferred_element_type=jnp.float32)
        contrib = {k: {"a": ein(recs[k]) / n, "g": ein(cots[k]) * n}
                   for k in self.blocks}
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return lt, grads, contrib

    def loss_grad_stats(self, params, batch, key):
        return self._lgs(params, batch, key)

    def loss(self, params, batch):
        """Mean loss alone (the forward pass)."""
        return self._loss(params, batch["x"], batch["y"])

    def _fisher_quad(self, params, batch, tangents):
        f = lambda p: logits(p, batch["x"], dtype=self.dtype)[0]
        z, lin = jax.linearize(f, params)
        zd = jnp.stack([lin(t) for t in tangents]).astype(jnp.float32)
        p = jax.nn.sigmoid(z.astype(jnp.float32))
        r = p * (1.0 - p)
        return jnp.einsum("no,mno,kno->mk", r, zd, zd) / z.shape[0]

    def fisher_quad(self, params, batch, tangents):
        return self._quad(params, batch, list(tangents))
