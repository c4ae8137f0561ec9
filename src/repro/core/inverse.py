"""Structured Fisher-block inverses + factored Tikhonov damping (S4.2, S6.3).

Damping (paper eqn. 7): each block's factors are damped as
``(Ā + π γ I) ⊗ (G + γ/π I)`` with the trace-norm choice
``π = sqrt( (tr Ā / d_A) / (tr G / d_G) )``.

Inversion methods:
  * ``eigh``  — exact symmetric eigendecomposition (fallback / reference)
  * ``ns``    — Newton–Schulz matmul-only iteration (MXU-native; the paper's
                own S8 pointer to Pan & Schreiber 1991), hot-startable from
                the previous inverse
  * ``solve`` — (used only in tests) dense jnp.linalg.inv

Eigenbasis (EKFAC) path — George et al., 1806.03884: instead of damped
factor *inverses*, :func:`eigen_pair_state` keeps the Kronecker
**eigenbases** ``Q_A, Q_G`` on the amortized T3 schedule plus a per-entry
diagonal in that basis.  The diagonal splits into ``s`` (second moments,
re-estimated every step from the rotated gradient — :func:`eigen_rescale`)
and ``damp`` (the factored-Tikhonov diagonal ``(γ/π)λ_A + πγλ_G + γ²``,
amortized with the bases), so right after a refresh
``s + damp = (λ_A + πγ)(λ_G + γ/π)`` and :func:`apply_eigen` reproduces the
``eigh`` inverse exactly, while between refreshes the scaling tracks the
live gradients at diagonal cost.

All routines are batched over arbitrary leading dims (layer stacks, experts,
TP blocks) — inverses of stacked factors are one batched kernel.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.tags import LayerMeta

_TINY = 1e-20


# ---------------------------------------------------------------------------
# traces / pi
# ---------------------------------------------------------------------------

def fixed_order_sum(x):
    """Sum over the last axis in one fixed pairwise order.

    A reduce's summation order is the compiler's choice, and it differs
    between a top-level program and a ``lax.cond`` branch — the shape the
    sharded refresh runs each block in.  The sums that feed ``eigh`` and
    Newton–Schulz therefore add halves elementwise instead, so both
    refresh executors see bitwise-identical inputs (docs/distributed.md).
    """
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def factor_trace(arr, kind: str):
    """Total trace per (stack/expert) index. Returns shape = lead dims."""
    if kind == "diag":
        return fixed_order_sum(arr)
    tr = fixed_order_sum(jnp.diagonal(arr, axis1=-2, axis2=-1))
    if kind == "block":
        tr = fixed_order_sum(tr)           # sum over the block axis
    return tr


def pi_trace(a, a_kind, a_dim, g, g_kind, g_dim):
    """Paper S6.3 trace-norm pi, batched over lead dims.  The means scale
    by a reciprocal: jit rewrites a division by a constant that way, so
    spelling it out keeps every executor on the same bits."""
    a_tr = factor_trace(a, a_kind) * (1.0 / a_dim)
    g_tr = factor_trace(g, g_kind) * (1.0 / g_dim)
    return jnp.sqrt(jnp.maximum(a_tr, _TINY) / jnp.maximum(g_tr, _TINY))


# ---------------------------------------------------------------------------
# damped inverse of one factor
# ---------------------------------------------------------------------------

def _add_damp(arr, kind: str, damp):
    """damp has the lead-dims shape (no block axis)."""
    if kind == "diag":
        return arr + damp[..., None]
    d = arr.shape[-1]
    eye = jnp.eye(d, dtype=arr.dtype)
    if kind == "block":
        return arr + damp[..., None, None, None] * eye
    return arr + damp[..., None, None] * eye


def eigh_inverse(m, floor: float = 1e-12):
    w, v = jnp.linalg.eigh(m)
    wi = 1.0 / jnp.maximum(w, floor)
    return jnp.einsum("...ij,...j,...kj->...ik", v, wi, v)


def ns_inverse(m, iters: int, x0=None):
    """Newton–Schulz: X <- X (2I - M X).  m: (..., d, d) SPD (damped)."""
    d = m.shape[-1]
    eye = jnp.eye(d, dtype=m.dtype)
    lam = jnp.max(fixed_order_sum(jnp.abs(m)), axis=-1)        # >= sigma_max
    cold = eye / lam[..., None, None]
    if x0 is None:
        x = cold
    else:
        # safeguard the hot start: ||I - M x0||_inf < 1 required
        r = eye - m @ x0
        bad = jnp.max(fixed_order_sum(jnp.abs(r)), axis=-1) >= 1.0
        x = jnp.where(bad[..., None, None], cold, x0)

    def body(_, x):
        return x @ (2.0 * eye - m @ x)

    x = jax.lax.fori_loop(0, iters, body, x)
    return 0.5 * (x + jnp.swapaxes(x, -1, -2))


def factor_inverse(arr, kind: str, damp, *, method: str = "eigh",
                   iters: int = 12, prev=None):
    """Inverse of (factor + damp*I); diag kind returns the reciprocal."""
    arr = _add_damp(arr.astype(jnp.float32), kind, jnp.asarray(damp, jnp.float32))
    if kind == "diag":
        return 1.0 / jnp.maximum(arr, _TINY)
    if method == "eigh":
        return eigh_inverse(arr)
    if method == "ns":
        return ns_inverse(arr, iters, prev)
    return jnp.linalg.inv(arr)


def damped_pair_inverse(meta: LayerMeta, a, g, gamma, *, method="eigh",
                        iters=12, prev: Optional[Dict] = None):
    """Both inverses of one layer block under factored Tikhonov damping."""
    pi = pi_trace(a, meta.a_kind, meta.a_dim, g, meta.g_kind, meta.g_dim)
    a_inv = factor_inverse(a, meta.a_kind, pi * gamma, method=method,
                           iters=iters,
                           prev=None if prev is None else prev.get("a_inv"))
    g_inv = factor_inverse(g, meta.g_kind, gamma / pi, method=method,
                           iters=iters,
                           prev=None if prev is None else prev.get("g_inv"))
    return {"a_inv": a_inv, "g_inv": g_inv}


# ---------------------------------------------------------------------------
# eigenbasis (EKFAC) state:  F ≈ (Q_A ⊗ Q_G) diag(s + damp) (Q_A ⊗ Q_G)ᵀ
# ---------------------------------------------------------------------------

def eigh_basis(arr, kind: str):
    """Eigendecomposition of one factor: ``(q, w)``.

    ``q`` is the orthonormal eigenbasis (``None`` for diag factors — already
    in their eigenbasis, so rotation is the identity); ``w`` the eigenvalues
    flattened to ``(*lead, dim)`` (block factors concatenate their per-block
    spectra, matching the flat layout :func:`apply_eigen` rotates into).
    """
    if kind == "diag":
        return None, jnp.maximum(arr, 0.0)
    w, q = jnp.linalg.eigh(arr)
    if kind == "block":
        w = w.reshape(*w.shape[:-2], -1)
    return q, jnp.maximum(w, 0.0)          # clip eigh's tiny negatives (PSD)


def _rot_left(q, kind: str, v, adjoint: bool):
    """Rotate along d_in: ``Qᵀ v`` (adjoint) or ``Q v``; None = identity."""
    if q is None:
        return v
    return _mul_left(jnp.swapaxes(q, -1, -2) if adjoint else q, kind, v)


def _rot_right(q, kind: str, v, adjoint: bool):
    """Rotate along d_out: ``v Q`` (adjoint) or ``v Qᵀ``; None = identity."""
    if q is None:
        return v
    return _mul_right(q if adjoint else jnp.swapaxes(q, -1, -2), kind, v)


def rotate_eigen(meta: LayerMeta, qa, qg, v, *, adjoint: bool):
    """``Q_Aᵀ V Q_G`` (adjoint=True: into the eigenbasis) or ``Q_A V Q_Gᵀ``."""
    u = _rot_left(qa, meta.a_kind, v, adjoint)
    return _rot_right(qg, meta.g_kind, u, adjoint)


def _eigen_parts(meta: LayerMeta, a, g):
    """The gamma-independent pieces: bases, eigenvalue column/row, pi."""
    qa, wa = eigh_basis(a, meta.a_kind)
    qg, wg = eigh_basis(g, meta.g_kind)
    pi = pi_trace(a, meta.a_kind, meta.a_dim, g, meta.g_kind, meta.g_dim)
    return qa, qg, wa[..., :, None], wg[..., None, :], pi


def _eigen_damp(wa_col, wg_row, pi, gamma):
    """Factored-Tikhonov diagonal ``(γ/π)λ_A + πγλ_G + γ²`` (broadcastable)."""
    gamma = jnp.asarray(gamma, jnp.float32)
    return ((gamma / pi)[..., None, None] * wa_col
            + (pi * gamma)[..., None, None] * wg_row + jnp.square(gamma))


def eigen_pair_state(meta: LayerMeta, a, g, gamma):
    """Amortized EKFAC state of one block: bases + eigenbasis diagonals.

    Returns ``{"qa", "qg", "s", "damp"}`` where ``s`` is initialized to the
    Kronecker eigenvalue products ``λ_A,i λ_G,j`` (the exact Fisher diagonal
    in this basis) and ``damp`` carries the factored-Tikhonov cross terms, so
    dividing by ``s + damp`` equals the ``eigh`` factor-inverse apply until
    :func:`eigen_rescale` starts re-estimating ``s`` from live gradients.
    """
    qa, qg, wa_col, wg_row, pi = _eigen_parts(meta, a, g)
    s = wa_col * wg_row
    damp = jnp.broadcast_to(_eigen_damp(wa_col, wg_row, pi, gamma), s.shape)
    return {"qa": qa, "qg": qg, "s": s, "damp": damp}


def eigen_pair_multi(meta: LayerMeta, a, g, gammas):
    """Candidate-stacked eigen states for the S6.6 gamma sweep, sharing ONE
    eigendecomposition per factor — only ``damp`` depends on gamma, so the
    bases/diagonals are broadcast across the leading candidate axis instead
    of recomputed per candidate."""
    qa, qg, wa_col, wg_row, pi = _eigen_parts(meta, a, g)
    s = wa_col * wg_row
    damp = jax.vmap(lambda gm: jnp.broadcast_to(
        _eigen_damp(wa_col, wg_row, pi, gm), s.shape))(gammas)
    n = gammas.shape[0]
    tile = lambda x: (None if x is None
                      else jnp.broadcast_to(x[None], (n, *x.shape)))
    return {"qa": tile(qa), "qg": tile(qg), "s": tile(s), "damp": damp}


def eigen_rescale(meta: LayerMeta, eig, grad, eps):
    """Per-step EKFAC diagonal update: ``s ← εs + (1−ε)(Q_Aᵀ ∇ Q_G)²``."""
    t = rotate_eigen(meta, eig["qa"], eig["qg"],
                     grad.astype(jnp.float32), adjoint=True)
    return dict(eig, s=eps * eig["s"] + (1.0 - eps) * jnp.square(t))


def apply_eigen(meta: LayerMeta, eig, v, floor: float = 1e-12):
    """``U = Q_A [ (Q_Aᵀ V Q_G) / (s + damp) ] Q_Gᵀ``; v shaped like W."""
    t = rotate_eigen(meta, eig["qa"], eig["qg"],
                     v.astype(jnp.float32), adjoint=True)
    t = t / (eig["s"] + eig["damp"] + floor)
    return rotate_eigen(meta, eig["qa"], eig["qg"], t, adjoint=False)


# ---------------------------------------------------------------------------
# preconditioning:  U = Ā⁻¹ V G⁻¹   (V stored (d_in[, +1], d_out) like W)
# ---------------------------------------------------------------------------

def _mul_left(inv, kind: str, v):
    """Multiply along the d_in (second-to-last) axis of v."""
    if kind == "diag":
        return v * inv[..., :, None]
    if kind == "block":
        nb, db = inv.shape[-3], inv.shape[-1]
        lead = v.shape[:-2]
        vr = v.reshape(*lead, nb, db, v.shape[-1])
        out = jnp.einsum("...nij,...njk->...nik", inv, vr)
        return out.reshape(*lead, nb * db, v.shape[-1])
    return jnp.einsum("...ij,...jk->...ik", inv, v)


def _mul_right(inv, kind: str, v):
    """Multiply along the d_out (last) axis of v."""
    if kind == "diag":
        return v * inv[..., None, :]
    if kind == "block":
        nb, db = inv.shape[-3], inv.shape[-1]
        vr = v.reshape(*v.shape[:-1], nb, db)        # (..., d_in, nb, db)
        out = jnp.einsum("...inj,...njk->...ink", vr, inv)
        return out.reshape(*v.shape)
    return jnp.einsum("...ij,...jk->...ik", v, inv)


def apply_block_inverse(meta: LayerMeta, inv: Dict, v):
    """U = Ā⁻¹ V G⁻¹ with per-kind structure; v shaped like the weight."""
    u = _mul_left(inv["a_inv"], meta.a_kind, v.astype(jnp.float32))
    return _mul_right(inv["g_inv"], meta.g_kind, u)
