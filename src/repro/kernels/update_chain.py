"""Fused precondition + momentum + norm accumulation (paper S4.2 + S7):

    D = alpha * (A^-1 V G^-1) + mu * M,      ||D||² as a kernel by-product

The fixed-learning-rate update chain (``use_rescale=False``) used to run as
three separate ops — precondition, momentum axpy, global-norm clip — each
materializing a weight-shaped intermediate in HBM and the clip *re-reading*
the finished update just to take its norm.  Here the whole chain is two
kernels:

  * ``T = V G^-1`` — the plain tiled matmul, and
  * one epilogue kernel that computes ``alpha·(A^-1 T) + mu·M`` and, while
    the finished ``(bm, bn)`` tile is still in VMEM, accumulates its squared
    Frobenius norm into a per-tile partials grid.

The caller sums the partials (a ``(grid_m, grid_n)`` array, a few hundred
floats) and folds the clip factor ``min(1, c/||D||)`` into the parameter
apply — the update tensor itself is written exactly once and never re-read.
``alpha``/``mu`` ride scalar prefetch, so the optimizer's traced step sizes
never recompile; tile sizes come from the autotuner when enabled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.matmul import matmul

DEFAULT_BLOCK = 128


def _kernel(am_ref, a_ref, t_ref, m_ref, o_ref, sq_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], t_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        out = (am_ref[0] * acc_ref[...]
               + am_ref[1] * m_ref[...].astype(jnp.float32))
        o_ref[...] = out.astype(o_ref.dtype)
        sq_ref[...] = jnp.sum(out * out).reshape(sq_ref.shape)


def axpy_momentum(a_inv, t, mom, alpha, mu, *, bm: int = DEFAULT_BLOCK,
                  bn: int = DEFAULT_BLOCK, bk: int = DEFAULT_BLOCK,
                  interpret=None):
    """``D = alpha·(a_inv @ t) + mu·mom`` plus per-tile ``Σ D²`` partials.

    a_inv: (M, K); t: (K, N); mom: (M, N).  Returns ``(D, sq_partials)``
    with ``sq_partials`` shaped ``(M//bm, N//bn, 1, 1)``: one ``(1, 1)``
    block per tile, so the block spans the array's last two dims as the
    TPU lowering requires.  ``alpha``/``mu`` may be
    python floats or traced jnp scalars (scalar prefetch).
    """
    m, k = a_inv.shape
    k2, n = t.shape
    assert k == k2 and mom.shape == (m, n), (a_inv.shape, t.shape, mom.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a_inv.shape,
                                                         t.shape, (bm, bn, bk))
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    am = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(mu, jnp.float32)])
    kernel = functools.partial(_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, am: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk, am: (kk, j)),
                pl.BlockSpec((bm, bn), lambda i, j, kk, am: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j, kk, am: (i, j)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, j, kk, am: (i, j, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m // bm, n // bn, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(am, a_inv, t, mom)


def precond_momentum(a_inv, v, g_inv, mom, *, alpha, mu,
                     block: int = DEFAULT_BLOCK, interpret=None):
    """The fused chain for one Kronecker block:

        D = alpha · (A^-1 V G^-1) + mu · mom,   plus ``Σ D²`` (a scalar)

    a_inv: (d_in, d_in); v: (d_in, d_out); g_inv: (d_out, d_out);
    mom: (d_in, d_out).  Returns ``(D, sqnorm)``.
    """
    t = matmul(v.astype(jnp.float32), g_inv, bm=block, bn=block, bk=block,
               interpret=interpret)
    d, sq = axpy_momentum(a_inv, t, mom, alpha, mu, bm=block, bn=block,
                          bk=block, interpret=interpret)
    return d, jnp.sum(sq)
