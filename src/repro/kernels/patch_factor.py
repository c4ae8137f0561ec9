"""Fused im2col + decayed KFC patch-factor accumulation (1602.01407 §3):

    Ā_new = beta * Ā_old + alpha * PᵀP,    P = im2col(x)

without ever materializing the ``(B·T_out, K·C)`` patch matrix ``P`` in HBM.
The A-factor of a 1-D conv has a tap-pair block structure

    Ā[(k₁,c₁), (k₂,c₂)] = Σ_{b,t} x[b, t·s + k₁, c₁] · x[b, t·s + k₂, c₂]

so the kernel grids over tap pairs ``(k₁, k₂)`` and streams time tiles of
the *raw* input through VMEM once per pair: each step loads two consecutive
``(bt·s, C)`` time blocks (the second is the halo for the tap shift)
into one VMEM buffer, reads the tap-shifted, stride-subsampled rows with
one strided load, and feeds the MXU a ``(bt, C)ᵀ @ (bt, C)`` rank-update.
The decay blend is the epilogue of the last step; ``alpha``/``beta`` ride
scalar prefetch so the optimizer's traced ``ε = min(1 − 1/k, ε_max)``
never recompiles.  The factor is viewed as ``(tap, tap, C, C)`` tiles, so
each block spans its array's last two dims as the TPU lowering requires.

The homogeneous bias row/column (``ā = [patch; 1]``) is a spatial *sum* of
the raw input — O(T·C), not O(T·C²·K²) — so :func:`patch_factor_update`
computes the border with cheap strided slices and splices it around the
kernel's core.  Shapes that don't tile (see :func:`patch_tile_ok`) return
``None`` and the caller falls back to the einsum path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret, tile_ok


def conv_pad_amounts(t: int, k: int, stride: int, padding: str):
    """(lo, hi) zero-padding of one conv dim under lax "SAME"/"VALID"."""
    if padding == "VALID":
        return 0, 0
    out = -(-t // stride)
    total = max((out - 1) * stride + k - t, 0)
    return total // 2, total - total // 2


def patch_tile_ok(c: int, t_out: int, taps: int = 1,
                  stride: int = 1) -> bool:
    """Whether the fused patch-factor kernel applies: one clean ``(C, C)``
    MXU tile per tap pair, a positive tiling output-position count, and
    taps that fit inside one time block (the halo covers one block only)."""
    return (0 < c <= 128 and c % 8 == 0 and t_out > 0 and tile_ok(t_out)
            and taps <= min(128, t_out) * stride)


def _kernel(ab_ref, x0_ref, x1_ref, c_ref, o_ref, buf_ref, acc_ref, *, bt,
            stride, n_steps):
    ki = pl.program_id(0)
    kj = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # two consecutive time blocks: the halo for the (sub-block) tap shifts
    blk = x0_ref.shape[1]
    buf_ref[pl.ds(0, blk), :] = x0_ref[0]
    buf_ref[pl.ds(blk, blk), :] = x1_ref[0]

    def rows(k):
        # patch rows t·stride + k for t < bt: a strided read at the tap offset
        return buf_ref[pl.ds(k, bt, stride=stride), :]

    acc_ref[...] += jax.lax.dot_general(
        rows(ki), rows(kj), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(r == n_steps - 1)
    def _done():
        o_ref[0, 0] = (ab_ref[0] * acc_ref[...]
                       + ab_ref[1] * c_ref[0, 0].astype(jnp.float32)
                       ).astype(o_ref.dtype)


def patch_factor(x, c, *, taps: int, stride: int, t_out: int, alpha, beta,
                 bt: int = 128, interpret=None):
    """x: (B, T_pad, C) conv-padded raw input; c: (K·C, K·C) running factor.

    Patch row ``(b, t, k)`` is ``x[b, t·stride + k]`` for ``t < t_out``;
    ``alpha``/``beta`` may be python floats or traced jnp scalars.
    """
    b, t_in, ch = x.shape
    d = taps * ch
    assert c.shape == (d, d), (c.shape, d)
    bt = min(bt, t_out)
    assert t_out % bt == 0 and taps <= bt * stride, (t_out, bt, taps, stride)
    nt = t_out // bt
    blk = bt * stride
    # one extra zero block so the halo read of the last tile stays in bounds
    full = (nt + 1) * blk
    assert t_in <= full, (t_in, full)
    if t_in < full:
        x = jnp.pad(x, ((0, 0), (0, full - t_in), (0, 0)))
    n_steps = b * nt
    ab = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(beta, jnp.float32)])
    kernel = functools.partial(_kernel, bt=bt, stride=stride, n_steps=n_steps)
    c4 = c.reshape(taps, ch, taps, ch).transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(taps, taps, n_steps),
            in_specs=[
                pl.BlockSpec((1, blk, ch),
                             lambda i, j, r, ab: (r // nt, r % nt, 0)),
                pl.BlockSpec((1, blk, ch),
                             lambda i, j, r, ab: (r // nt, r % nt + 1, 0)),
                pl.BlockSpec((1, 1, ch, ch), lambda i, j, r, ab: (i, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, ch, ch),
                                   lambda i, j, r, ab: (i, j, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2 * blk, ch), x.dtype),
                            pltpu.VMEM((ch, ch), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((taps, taps, ch, ch), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(ab, x, x, c4)
    return out.transpose(0, 2, 1, 3).reshape(d, d)


def patch_factor_update(x, old, meta, alpha, beta, *, bt: int = 128,
                        interpret=None,
                        autotune_mode: str = "off"):
    """The ``ConvKronecker`` A-side route: fused ``Ā ← β Ā + α P̂ᵀP̂`` for a
    1-D conv from the raw input, or ``None`` when the shape doesn't tile
    (the caller falls back to the einsum path).

    x: (B, T, C) raw (un-padded) input; old: (a_dim, a_dim) running factor
    with the homogeneous row/column last when ``meta.has_bias``.
    ``autotune_mode`` != "off" looks up a tuned time-tile ``bt``.
    """
    interpret = resolve_interpret(interpret)
    if len(meta.conv_spatial) != 1:
        return None
    (k,), (s,) = meta.conv_spatial, meta.conv_stride
    b, t, ch = x.shape
    from repro.models.conv import conv_out_len
    t_out = conv_out_len(t, k, s, meta.conv_pad)
    if not patch_tile_ok(ch, t_out, k, s):
        return None
    if autotune_mode != "off":
        from repro.kernels.autotune import tuned
        cfg = tuned("patch_factor", (t_out, ch, k, s), x.dtype,
                    interpret=interpret, mode=autotune_mode)
        if cfg:
            bt = cfg["bt"]
    lo, hi = conv_pad_amounts(t, k, s, meta.conv_pad)
    xp = jnp.pad(x, ((0, 0), (lo, hi), (0, 0))) if lo or hi else x
    d = k * ch
    core_old = old[:d, :d] if meta.has_bias else old
    core = patch_factor(xp, core_old, taps=k, stride=s, t_out=t_out,
                        alpha=alpha, beta=beta, bt=bt, interpret=interpret)
    if not meta.has_bias:
        return core
    # homogeneous border: Σ_t patch (per tap, a strided slice sum) + count
    m = jnp.concatenate(
        [jnp.sum(xp[:, kk:kk + t_out * s:s, :].astype(jnp.float32), (0, 1))
         for kk in range(k)])
    cnt = jnp.float32(b * t_out)
    row = beta * old[d, :d] + alpha * m
    corner = beta * old[d, d] + alpha * cnt
    col = beta * old[:d, d] + alpha * m
    top = jnp.concatenate([core, col[:, None]], axis=1)
    bot = jnp.concatenate([row, corner[None]])[None, :]
    return jnp.concatenate([top, bot], axis=0)
