"""KFC convolution curvature blocks (Grosse & Martens, arXiv:1602.01407).

A conv layer's Fisher block is Kronecker-factored over *patches*: with the
weight stored as a ``(prod(K)·C [+1], d_out)`` matrix over tap-major im2col
features (see :mod:`repro.models.conv`), the approximation is

  * ``Ā`` — the spatially-averaged patch second moment
    ``(1/N) Σ_{b,t} â_{bt} â_{bt}ᵀ`` with the homogeneous coordinate
    ``â = [patch; 1]`` carrying the bias row/column, and
  * ``G``  — the pre-activation gradient second moment averaged over the
    same spatial locations, ``(1/N) Σ_{b,t} g_{bt} g_{bt}ᵀ``

— i.e. every spatial output location is a "token", exactly how the dense
blocks treat sequence positions (KFC's SUA assumption: spatially
uncorrelated derivatives).  Both sides use the optimizer's global-N
normalization; the c·Ā ⊗ (1/c)·G ambiguity this leaves is annihilated by
the factored-Tikhonov trace norm π (S6.3), so the damped preconditioner is
normalization-independent.

The record carries only the RAW conv input (``{"cx": x}`` from
``Tagger.tag_conv``); patches are extracted here — on the XLA path via
``jax.lax.conv_general_dilated_patches``, on the Pallas path fused into the
factor accumulation itself (:mod:`repro.kernels.patch_factor`), so the
im2col buffer is never materialized in HBM during the stats pass.  Since
the weight is a plain matrix, everything else — damped ``eigh``/``ns``
inverses, the EKFAC eigen state + per-step ``rescale_step``, and the Pallas
``precondition`` / ``rotate_rescale`` routes — is inherited from
:class:`DenseKronecker` unchanged.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.blocks.base import register
from repro.core.blocks.kron import DenseKronecker
from repro.kernels.patch_factor import patch_factor_update


@register
class ConvKronecker(DenseKronecker):
    """KFC conv block: patch-factor statistics over spatial locations."""

    kinds = ("conv",)
    priority = 10

    def patches(self, rec):
        """im2col of the recorded raw input, flattened over (batch, space),
        with the homogeneous coordinate appended when the layer has a bias.
        A record already in dense form (``{"a": patches}``, as produced by
        the delegating paths below) passes through unchanged."""
        if "cx" not in rec:
            return rec["a"]
        from repro.models.conv import append_homog, extract_patches
        m = self.meta
        p = extract_patches(rec["cx"], m.conv_spatial, m.conv_stride,
                            m.conv_pad)
        p = p.reshape(-1, p.shape[-1])
        return append_homog(p) if m.has_bias else p

    def stats_contrib(self, rec, gprobe, batch, n):
        # dense-form record over the extracted patches; an already-contracted
        # record (fused_stats, {"aa"}) passes straight through — the shared
        # KroneckerPair numerics handle every per-side factor kind
        dense_rec = rec if "aa" in rec else {"a": self.patches(rec)}
        return super().stats_contrib(dense_rec, gprobe, batch, n)

    def update_factors(self, old, rec, gprobe, batch, n, eps):
        m = self.meta
        one = jnp.float32(1.0)
        a_new = None
        if (self.backend == "pallas" and not self.lead and m.a_kind == "full"
                and m.g_kind == "full" and "cx" in rec
                and rec["cx"].ndim == 3):
            # 1-D conv: fused im2col + factor update straight from the raw
            # input — the im2col buffer never hits HBM (declines to None on
            # shapes that don't tile)
            a_new = patch_factor_update(rec["cx"], old["a"], m,
                                        (one - eps) / n, eps,
                                        interpret=self._interpret(),
                                        autotune_mode=self.autotune_mode)
        if a_new is None:
            # everything else is exactly the dense route: pre-contracted
            # fused records pass through, 2-D patchifiers (their im2col is a
            # reshape, no blowup) and ragged shapes fall back inside
            # DenseKronecker over the extracted patches
            dense_rec = rec if "aa" in rec else {"a": self.patches(rec)}
            return super().update_factors(old, dense_rec, gprobe, batch, n,
                                          eps)
        # A fused; G identically to the dense route — cotangents of the
        # (1/N)-normalized sampled loss over every spatial location
        self._route("factor_update.a", True)
        return {"a": a_new, "g": self._g_side(old["g"], gprobe, n, eps)}
