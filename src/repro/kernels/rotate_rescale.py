"""Fused EKFAC eigenbasis apply (George et al. 1806.03884):

    U = Q_A [ (Q_Aᵀ V Q_G) / (s + lam) ] Q_Gᵀ

Rotate into the Kronecker eigenbasis, damped diagonal rescale, rotate back —
the eigen-mode analogue of :mod:`repro.kernels.precond`'s two-sided apply and
tiled the same way (tiles stream through VMEM; the (d_in, d_out) grad matrix
stays in HBM).  The middle product fuses the rescale into its epilogue via
:func:`matmul_rescale`, so the eigenbasis copy of the gradient is divided by
the damped diagonal as it is produced, never re-read.  ``lam`` rides a
scalar-prefetch operand and may be a traced value (the damping floor /
per-refresh λ), mirroring ``factor_update``'s traced decay ε.

Shapes must tile into the 128-blocks (``backend.tile_ok``); the curvature
blocks fall back to the einsum path in ``core.inverse.apply_eigen`` for
ragged shapes or ``kernel_backend="xla"``, so the backend knob never changes
results — only which kernels execute.
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


def _kernel(lam_ref, a_ref, b_ref, s_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...]
                      / (s_ref[...].astype(jnp.float32) + lam_ref[0])
                      ).astype(o_ref.dtype)


def matmul_rescale(a, b, s, lam, *, bm: int = DEFAULT_BLOCK,
                   bn: int = DEFAULT_BLOCK, bk: int = DEFAULT_BLOCK,
                   interpret=None):
    """``(A @ B) / (S + lam)`` — a: (M, K); b: (K, N); s: (M, N).

    ``lam`` may be a python float or a traced jnp scalar (scalar prefetch).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and s.shape == (m, n), (a.shape, b.shape, s.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape,
                                                         (bm, bn, bk))
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    lam = jnp.asarray(lam, jnp.float32).reshape(1)
    kernel = functools.partial(_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, lam: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk, lam: (kk, j)),
                pl.BlockSpec((bm, bn), lambda i, j, kk, lam: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, lam: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(lam, a, b, s)


def rotate_rescale(qa, v, qg, s, lam=0.0, *, block: int = DEFAULT_BLOCK,
                   interpret=None):
    """qa: (d_in, d_in); v: (d_in, d_out); qg: (d_out, d_out); s: (d_in, d_out).

    Four tiled matmuls; the rescale fuses into the second's epilogue.
    """
    t = matmul(qa.T, v.astype(jnp.float32), bm=block, bn=block, bk=block,
               interpret=interpret)                     # Q_Aᵀ V
    t = matmul_rescale(t, qg, s, lam, bm=block, bn=block, bk=block,
                       interpret=interpret)             # (· Q_G) / (s + lam)
    t = matmul(qa, t, bm=block, bn=block, bk=block, interpret=interpret)
    return matmul(t, qg.T, bm=block, bn=block, bk=block, interpret=interpret)
