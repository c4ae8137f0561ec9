"""Fused decayed Kronecker-factor accumulation (paper S5 + S8 task 4):

    C_new = beta * C_old + alpha * XᵀX

One kernel: the rank-N symmetric update never materializes Xᵀ or an
intermediate product in HBM — X tiles stream through VMEM twice with two
index maps, the MXU does (bk,bm)ᵀ@(bk,bn) per step, and the decay blend is
the epilogue of the last K step.

``alpha``/``beta`` arrive as a scalar-prefetch operand, so they may be traced
values — the optimizer's decay ``ε = min(1 − 1/k, ε_max)`` is a function of
the running stats count and changes every step without recompiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _kernel(ab_ref, xa_ref, xb_ref, c_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(xa_ref[...].T, xb_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = (ab_ref[0] * acc_ref[...]
                      + ab_ref[1] * c_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def factor_update(x, c, *, alpha, beta, bm: int = 128,
                  bn: int = 128, bk: int = 128, interpret=None):
    """x: (N, d) activations/gradients; c: (d, d) running factor.

    ``alpha``/``beta`` may be python floats or traced jnp scalars.
    """
    n, d = x.shape
    assert c.shape == (d, d)
    bm, bn, bk = min(bm, d), min(bn, d), min(bk, n)
    assert d % bm == 0 and d % bn == 0 and n % bk == 0, (x.shape, (bm, bn, bk))
    k_steps = n // bk
    grid = (d // bm, d // bn, k_steps)
    ab = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(beta, jnp.float32)])
    kernel = functools.partial(_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bk, bm), lambda i, j, kk, ab: (kk, i)),
                pl.BlockSpec((bk, bn), lambda i, j, kk, ab: (kk, j)),
                pl.BlockSpec((bm, bn), lambda i, j, kk, ab: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, ab: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(ab, x, x, c)
