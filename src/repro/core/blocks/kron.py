"""Kronecker-pair curvature blocks for dense linear maps (paper S3–S4.2).

Three concrete layouts, resolved from ``LayerMeta``'s per-side factor kinds:

  * :class:`DenseKronecker`    — both factors dense (``full``/``full``).
    The hot path: when ``kernel_backend == "pallas"`` and shapes tile, the
    decayed factor accumulation runs through the fused
    :func:`repro.kernels.factor_update.factor_update` kernel (vmapped over
    stacked layers) and the two-sided apply through
    :func:`repro.kernels.precond.precondition`.
  * :class:`BlockDiagKronecker` — at least one TP-blocked side (DESIGN §3).
  * :class:`DiagFactor`         — at least one diagonal side (dims above
    ``max_factor_dim``).

All three share the per-side numerics in ``core.factors`` / ``core.inverse``;
the subclasses differ in dispatch and in which paths may route to Pallas.
Ragged shapes (or sides without raw activations) fall back to the einsum
path, so the choice of backend never changes results — only kernels.  Each
block records the route every op took (``CurvatureBlock.routes``;
``route_counts`` sums them), so a fallback is visible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import factors as F
from repro.core.blocks.base import CurvatureBlock, register
from repro.kernels.backend import tile_ok
from repro.kernels.factor_update import factor_update
from repro.kernels.precond import precondition as precond_kernel
from repro.kernels.rotate_rescale import rotate_rescale
from repro.kernels.update_chain import precond_momentum as chain_kernel


class KroneckerPair(CurvatureBlock):
    """Shared statistics/inverse/apply logic for two-sided Kronecker blocks."""

    def stats_contrib(self, rec, gprobe, batch, n):
        m = self.meta
        if "aa" in rec:              # contracted in-forward (scan models /
            a_c = rec["aa"] / n      # fused_stats)
        else:
            a_c = F.outer_sum(rec["a"], m.a_kind, m.a_blocks,
                              expert=m.kind == "expert") / n
        if isinstance(gprobe, dict):
            # fused_stats: the backward already contracted Σ cot cotᵀ (see
            # repro.core.fused.apply_gprobe); same N-scaling as
            # g_from_cotangent
            g_c = gprobe["gg"] * float(n)
        else:
            g_c = F.g_from_cotangent(gprobe, m, n)
        return {"a": a_c, "g": g_c}


@register
class DiagFactor(KroneckerPair):
    """A diagonal factor on at least one side (vocab-scale dims)."""

    kinds = ("dense",)
    priority = 30

    @classmethod
    def handles(cls, meta):
        return "diag" in (meta.a_kind, meta.g_kind)


@register
class BlockDiagKronecker(KroneckerPair):
    """A TP-block-diagonal factor on at least one side."""

    kinds = ("dense",)
    priority = 20

    @classmethod
    def handles(cls, meta):
        return "block" in (meta.a_kind, meta.g_kind)


@register
class DenseKronecker(KroneckerPair):
    """Dense ``full``/``full`` Kronecker pair — the Pallas hot path."""

    kinds = ("dense",)
    priority = 10

    # -- fused stats accumulation (S5 through the factor_update kernel) --
    def _pallas_side(self, x, old, alpha, eps):
        """One side's fused ``C ← ε C + α XᵀX`` if X tiles, else None.
        Stacked (scanned) layers map the kernel over their lead dims."""
        if x is None:
            return None
        nl = len(self.lead)
        x2 = x.reshape(*x.shape[:nl], -1, x.shape[-1])
        if not tile_ok(*x2.shape[nl:]):
            return None
        cfg = self._tuned("factor_update", x2.shape[nl:], x2.dtype)
        fn = lambda xx, cc: factor_update(xx, cc, alpha=alpha, beta=eps,
                                          interpret=self._interpret(), **cfg)
        for _ in range(nl):
            fn = jax.vmap(fn)
        return fn(x2, old)

    def _g_side(self, old_g, gprobe, n, eps):
        """G side of the decayed blend: cotangents of the (1/N)-normalized
        sampled loss; per-token g = N·cot, so G = (1/N) Σ g gᵀ = N Σ cot cotᵀ.
        A fused ``{"gg"}`` gprobe arrives pre-contracted by the backward."""
        one = jnp.float32(1.0)
        g_new = None
        if not isinstance(gprobe, dict):
            g_new = self._pallas_side(jax.lax.stop_gradient(gprobe), old_g,
                                      (one - eps) * n, eps)
        self._route("factor_update.g", g_new is not None)
        if g_new is None:
            g_c = (gprobe["gg"] * float(n) if isinstance(gprobe, dict)
                   else F.g_from_cotangent(gprobe, self.meta, n))
            g_new = eps * old_g + (one - eps) * g_c
        return g_new

    def update_factors(self, old, rec, gprobe, batch, n, eps):
        if self.backend != "pallas":
            return super().update_factors(old, rec, gprobe, batch, n, eps)
        one = jnp.float32(1.0)
        # A side: fuse only when the raw activations were recorded (models
        # that contract Ā in-forward never materialize X outside the scan)
        a_new = self._pallas_side(rec.get("a"), old["a"], (one - eps) / n, eps)
        self._route("factor_update.a", a_new is not None)
        if a_new is None:
            a_c = self.stats_contrib(rec, gprobe, batch, n)["a"]
            a_new = eps * old["a"] + (one - eps) * a_c
        return {"a": a_new, "g": self._g_side(old["g"], gprobe, n, eps)}

    # -- two-sided apply through the precond kernel ---------------------
    def precondition(self, inv, v):
        m = self.meta
        if (self.backend == "pallas" and tile_ok(m.a_dim, m.g_dim)
                and v.shape[-2:] == (m.a_dim, m.g_dim)):
            cfg = self._tuned("precond", (m.a_dim, m.g_dim), jnp.float32)
            fn = lambda a_i, vv, g_i: precond_kernel(
                a_i, vv, g_i, interpret=self._interpret(), **cfg)
            for _ in range(v.ndim - 2):      # vmap over stack/expert dims
                fn = jax.vmap(fn)
            self._route("precond", True)
            return fn(inv["a_inv"], v.astype(jnp.float32), inv["g_inv"])
        return super().precondition(inv, v)

    # -- fused fixed-lr update chain through the update_chain kernel ----
    def precond_momentum(self, inv, v, mom, alpha, mu, eigen: bool = False):
        m = self.meta
        if (not eigen and self.backend == "pallas"
                and tile_ok(m.a_dim, m.g_dim)
                and v.shape == (m.a_dim, m.g_dim)):
            cfg = self._tuned("update_chain", (m.a_dim, m.g_dim),
                              jnp.float32)
            self._route("update_chain", True)
            return chain_kernel(inv["a_inv"], v.astype(jnp.float32),
                                inv["g_inv"], mom, alpha=alpha, mu=mu,
                                interpret=self._interpret(), **cfg)
        return super().precond_momentum(inv, v, mom, alpha, mu, eigen)

    # -- eigenbasis apply through the rotate_rescale kernel -------------
    def precondition_eigen(self, eig, v):
        m = self.meta
        if (self.backend == "pallas" and tile_ok(m.a_dim, m.g_dim)
                and v.shape[-2:] == (m.a_dim, m.g_dim)):
            cfg = self._tuned("rotate_rescale", (m.a_dim, m.g_dim),
                              jnp.float32)
            fn = lambda qa, vv, qg, sd: rotate_rescale(
                qa, vv, qg, sd, lam=1e-12, interpret=self._interpret(),
                **cfg)
            for _ in range(v.ndim - 2):      # vmap over stack/expert dims
                fn = jax.vmap(fn)
            self._route("rotate_rescale", True)
            return fn(eig["qa"], v.astype(jnp.float32), eig["qg"],
                      eig["s"] + eig["damp"])
        return super().precondition_eigen(eig, v)
