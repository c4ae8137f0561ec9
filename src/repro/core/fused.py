"""Backward-pass fusion of the factor statistics (paper S5, one pass).

The two-pass layout records raw activations in the forward and raw probe
cotangents out of the backward, then makes a *second* sweep over both to
form ``Ā += ā āᵀ`` / ``G += g gᵀ`` — every recorded ``(N, d)`` tensor is
written to HBM by the stats pass and read back by ``update_factors``.  With
``KFACConfig.fused_stats`` the contractions ride the passes themselves:

  * **A side** — the ``Tagger`` contract hook (the mechanism the scan models
    already use) records ``{"aa": Σ ā āᵀ}`` in-forward;
    :func:`dense_a_contract` / :func:`conv_a_contract` build the per-layer
    contraction, routing through the Pallas ``factor_update`` /
    ``patch_factor`` kernels when shapes tile.
  * **G side** — :func:`apply_gprobe`, a custom-VJP identity whose backward
    emits ``{"gg": Σ cot cotᵀ}`` as the probe's cotangent: the per-example
    ``dL/ds`` is contracted the moment the VJP produces it, while it is
    still live, instead of being materialized as an ``(N, d_out)`` probe
    cotangent and re-read.

Blocks see ``{"aa": ...}`` records and ``{"gg": ...}`` gprobes and skip
straight to the decayed blend — numerically the same contraction (same
einsum / same kernel) over the same values, so fused runs sit inside the
golden envelopes (``tests/test_autotune.py`` pins allclose per inv_mode).

Eligibility (enforced by :func:`fused_eligible`, wired in ``KFACEngine``):
dense/conv layers with full/full factors and no stack/expert lead dims.
``inv_mode="tridiag"`` disables fusion entirely — the chain's cross moments
need the raw per-layer records.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.tags import LayerMeta
from repro.kernels.autotune import tuned
from repro.kernels.backend import tile_ok
from repro.kernels.factor_update import factor_update


def fused_eligible(meta: LayerMeta) -> bool:
    """Layers whose stats can contract in-pass: plain dense/conv maps with
    full two-sided factors and no scan-stack / expert lead dims (stacked
    layers record through inner scan Taggers; their probes carry lead dims
    the per-layer contraction cannot see)."""
    return (meta.kind in ("dense", "conv") and meta.n_stack == 0
            and meta.n_expert == 0 and meta.a_kind == "full"
            and meta.g_kind == "full")


def _xtx(x2, backend: str, interpret: bool, mode: str):
    """``Σ xᵀx`` over rows — the Pallas rank-update kernel when the shape
    tiles, else the same f32-accumulated einsum ``F.outer_sum`` uses (so the
    xla fused path is bitwise the unfused contraction)."""
    if backend == "pallas" and tile_ok(*x2.shape):
        cfg = tuned("factor_update", x2.shape, x2.dtype,
                    interpret=interpret, mode=mode) or {}
        zero = jnp.zeros((x2.shape[1], x2.shape[1]), jnp.float32)
        return factor_update(x2, zero, alpha=1.0, beta=0.0,
                             interpret=interpret, **cfg)
    return jnp.einsum("nd,ne->de", x2, x2,
                      preferred_element_type=jnp.float32)


def dense_a_contract(meta: LayerMeta, backend: str, interpret: bool,
                     mode: str):
    """In-forward Ā contraction for a dense layer: ``ā`` (..., a_dim) →
    ``Σ ā āᵀ`` (a_dim, a_dim), recorded as ``{"aa": ...}``."""

    def fn(a):
        return _xtx(a.reshape(-1, a.shape[-1]), backend, interpret, mode)

    return fn


def conv_a_contract(meta: LayerMeta, backend: str, interpret: bool,
                    mode: str):
    """In-forward Ā contraction for a KFC conv layer, from the RAW input:
    the fused im2col+rank-update kernel when the 1-D shape tiles, else
    explicit patches through the shared einsum."""

    def fn(x):
        if backend == "pallas" and x.ndim == 3:
            from repro.kernels.patch_factor import patch_factor_update
            zero = jnp.zeros((meta.a_dim, meta.a_dim), jnp.float32)
            out = patch_factor_update(x, zero, meta, 1.0, 0.0,
                                      interpret=interpret,
                                      autotune_mode=mode)
            if out is not None:
                return out
        from repro.models.conv import append_homog, extract_patches
        p = extract_patches(x, meta.conv_spatial, meta.conv_stride,
                            meta.conv_pad)
        p = p.reshape(-1, p.shape[-1])
        if meta.has_bias:
            p = append_homog(p)
        return _xtx(p, backend, interpret, mode)

    return fn


def g_contract(meta: LayerMeta, backend: str, interpret: bool, mode: str):
    """In-backward G contraction: probe cotangent ``ds`` (..., g_dim) →
    ``Σ cot cotᵀ`` (g_dim, g_dim), delivered as the ``{"gg": ...}`` probe
    cotangent by :func:`apply_gprobe`."""

    def fn(ds):
        return _xtx(ds.reshape(-1, ds.shape[-1]), backend, interpret, mode)

    return fn


def einsum_gg(ds):
    """Backend-free fallback G contraction (a Tagger with a dict probe but
    no installed gcontract entry still produces correct statistics)."""
    d2 = ds.reshape(-1, ds.shape[-1])
    return jnp.einsum("nd,ne->de", d2, d2,
                      preferred_element_type=jnp.float32)


def gg_probe(meta: LayerMeta):
    """The fused layer's probe: a ``(g_dim, g_dim)`` zero the backward fills
    with the contracted second moment (instead of an ``(N, g_dim)`` zero
    filled with raw cotangents)."""
    return {"gg": jnp.zeros((meta.g_dim, meta.g_dim), jnp.float32)}


def apply_gprobe(s, probe_gg, contract):
    """Identity on ``s`` whose VJP emits ``contract(ds)`` as the cotangent
    of ``probe_gg`` — the zero-probe trick with the G-side contraction
    folded into the backward pass itself."""

    @jax.custom_vjp
    def f(s, p):
        return s

    def fwd(s, p):
        return s, None

    def bwd(_, ds):
        return ds, contract(jax.lax.stop_gradient(ds))

    f.defvjp(fwd, bwd)
    return f(s, probe_gg)
