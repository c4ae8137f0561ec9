"""Tiled MXU matmul with fused scale/accumulate epilogue.

    out = alpha * (A @ B) + beta * C

Grid is (M/bm, N/bn, K/bk) with the K axis innermost ("arbitrary" semantics);
a VMEM f32 scratch accumulates partial products, and the epilogue (scale +
decayed accumulate) runs on the last K step — this single kernel covers the
K-FAC factor update, the Newton–Schulz iteration's matmuls, and the
preconditioning products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


DEFAULT_BLOCK = 128


def _kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, alpha, beta, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        out = alpha * acc_ref[...]
        if beta != 0.0:
            out = out + beta * c_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def matmul(a, b, c=None, *, alpha: float = 1.0, beta: float = 0.0,
           bm: int = DEFAULT_BLOCK, bn: int = DEFAULT_BLOCK,
           bk: int = DEFAULT_BLOCK, out_dtype=jnp.float32,
           interpret=None):
    """a: (M, K); b: (K, N); c: optional (M, N). Dims must tile evenly."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape,
                                                         (bm, bn, bk))
    if c is None:
        c = jnp.zeros((m, n), out_dtype)
        beta = 0.0
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    kernel = functools.partial(_kernel, alpha=alpha, beta=beta,
                               k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, b, c)
