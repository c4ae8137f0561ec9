"""Curvature-block abstraction (paper S3–S4): one object per Fisher block.

The block-diagonal Fisher approximation assigns every tagged layer its own
Kronecker-factored block ``F_i ≈ Ā_i ⊗ G_i``.  :class:`CurvatureBlock` owns
everything per-layer the optimizer used to branch on ``meta.kind`` for:

  * factor layout + zero/identity initialization and sharding specs,
  * the per-step statistics contribution and decayed blend (S5),
  * the damped factor inverses (S4.2 / S6.3),
  * the preconditioner apply ``U = Ā⁻¹ V G⁻¹``.

Concrete subclasses live in :mod:`repro.core.blocks.kron` (dense /
TP-blocked / diagonal Kronecker pairs), :mod:`repro.core.blocks.special`
(embedding, LM head, MoE expert) and :mod:`repro.core.blocks.chain`
(the block-tridiagonal chain, S4.3).  Classes self-register against the
``LayerMeta.kind`` values they serve; :func:`build_blocks` resolves one
block instance per tagged layer.  Adding a new block family (EKFAC
eigenbasis blocks, convolution blocks, ...) is one new registered class —
no edits to the optimizer.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional, Type

import jax
import jax.numpy as jnp

from repro.core import factors as F
from repro.core import inverse as INV
from repro.core.tags import LayerMeta


class CurvatureBlock(abc.ABC):
    """One layer's Fisher block: layout, statistics, inverse, apply."""

    kinds: tuple = ()   # LayerMeta.kind values this class can serve
    priority: int = 0   # higher wins when several classes claim a kind

    def __init__(self, meta: LayerMeta, cfg):
        self.meta = meta
        self.cfg = cfg
        # op -> "pallas" | "einsum": the route each op took when it was last
        # traced (read by ``route_counts``; kernels can decline on shape)
        self.routes: Dict[str, str] = {}

    @classmethod
    def handles(cls, meta: LayerMeta) -> bool:
        """Refine registry dispatch beyond `kind` (e.g. on factor layout)."""
        return True

    # ------------------------------------------------------------------
    # kernel routing
    # ------------------------------------------------------------------
    @property
    def backend(self) -> str:
        return getattr(self.cfg, "kernel_backend", "xla")

    @property
    def autotune_mode(self) -> str:
        return getattr(self.cfg, "autotune", "off")

    @staticmethod
    def _interpret() -> bool:
        from repro.kernels.backend import resolve_interpret
        return resolve_interpret(None)

    def _route(self, op: str, kernel: bool) -> None:
        self.routes[op] = "pallas" if kernel else "einsum"

    def _tuned(self, kernel: str, shape, dtype) -> dict:
        """Autotuned tile kwargs for ``kernel`` on this problem, or ``{}``
        (kernel defaults) when tuning is off / no candidate is legal."""
        from repro.kernels.autotune import tuned
        return tuned(kernel, shape, dtype, interpret=self._interpret(),
                     mode=self.autotune_mode) or {}

    # ------------------------------------------------------------------
    # layout
    # ------------------------------------------------------------------
    @property
    def lead(self) -> tuple:
        m = self.meta
        lead = ()
        if m.n_stack:
            lead += (m.n_stack,)
        if m.n_expert:
            lead += (m.n_expert,)
        return lead

    def init_factors(self) -> Dict[str, Any]:
        m = self.meta
        return {
            "a": jnp.zeros(F.factor_shape(m.a_dim, m.a_kind, m.a_blocks,
                                          self.lead), jnp.float32),
            "g": jnp.zeros(F.factor_shape(m.g_dim, m.g_kind, m.g_blocks,
                                          self.lead), jnp.float32),
        }

    def identity_inverse(self) -> Dict[str, Any]:
        z = self.init_factors()

        def one(arr, kind):
            if kind == "diag":
                return jnp.ones_like(arr)
            return arr + jnp.eye(arr.shape[-1], dtype=jnp.float32)

        return {"a_inv": one(z["a"], self.meta.a_kind),
                "g_inv": one(z["g"], self.meta.g_kind)}

    def factor_specs(self, mesh) -> Dict[str, Any]:
        """Storage shardings for this block's factor/inverse state.

        Stacked/expert/block lead dims go over `model` where aligned; the
        matrix dim that CONTRACTS against the grad during preconditioning is
        FSDP-sharded over `data` (A: columns, G: rows) so ``U = Ā⁻¹ V G⁻¹``
        needs no gathers — just a small partial-sum all-reduce.
        """
        from jax.sharding import PartitionSpec as P
        from repro.utils.sharding import pick_shard
        m = self.meta

        def one(dim, kind, blocks, side):
            lead = []
            if m.n_stack:
                lead.append(None)
            if m.n_expert:
                lead.append(pick_shard(m.n_expert, mesh, "model"))
            if kind == "diag":
                return P(*lead, pick_shard(dim, mesh, "data"))
            if kind == "block":
                return P(*lead, pick_shard(blocks, mesh, "model"),
                         pick_shard(dim // blocks, mesh, "data"), None)
            if side == "a":
                return P(*lead, None, pick_shard(dim, mesh, "data"))
            return P(*lead, pick_shard(dim, mesh, "data"), None)

        return {"a": one(m.a_dim, m.a_kind, m.a_blocks, "a"),
                "g": one(m.g_dim, m.g_kind, m.g_blocks, "g")}

    # ------------------------------------------------------------------
    # statistics (S5)
    # ------------------------------------------------------------------
    @abc.abstractmethod
    def stats_contrib(self, rec, gprobe, batch, n: int) -> Dict[str, Any]:
        """This step's (1/N-normalized) factor contribution {"a", "g"}."""

    def update_factors(self, old, rec, gprobe, batch, n: int, eps):
        """Decayed blend ``C ← ε C + (1−ε) contrib``; ε may be traced."""
        self._route("factor_update.a", False)
        self._route("factor_update.g", False)
        return F.blend(old, self.stats_contrib(rec, gprobe, batch, n), eps)

    # ------------------------------------------------------------------
    # inverses (S4.2 / S6.3)
    # ------------------------------------------------------------------
    def damped_inverse(self, fac, gamma, *, method: str = "eigh",
                       iters: int = 12, prev: Optional[Dict] = None):
        return INV.damped_pair_inverse(self.meta, fac["a"], fac["g"], gamma,
                                       method=method, iters=iters, prev=prev)

    # ------------------------------------------------------------------
    # preconditioning
    # ------------------------------------------------------------------
    def precondition(self, inv, v):
        """``U = Ā⁻¹ V G⁻¹`` with this block's structure; v shaped like W."""
        self._route("precond", False)
        return INV.apply_block_inverse(self.meta, inv, v)

    def precond_momentum(self, inv, v, mom, alpha, mu, eigen: bool = False):
        """Fused update chain for the fixed-lr path (S4.2 + S7):
        ``D = alpha·precondition(v) + mu·mom`` plus ``Σ D²`` — the squared
        norm comes out of the same pass so the global-norm clip never
        re-reads the update.  Subclasses may serve this with one kernel."""
        self._route("update_chain", False)
        u = (self.precondition_eigen(inv, v) if eigen
             else self.precondition(inv, v))
        d = alpha * u.astype(jnp.float32) + mu * mom
        return d, jnp.sum(d * d)

    # ------------------------------------------------------------------
    # eigenbasis (EKFAC) path — George et al. 1806.03884
    # ------------------------------------------------------------------
    def eigen_state(self, fac, gamma):
        """Amortized refresh: factor eigenbases + eigenbasis diagonals
        ``{"qa", "qg", "s", "damp"}`` (``qa``/``qg`` None on diag sides)."""
        return INV.eigen_pair_state(self.meta, fac["a"], fac["g"], gamma)

    def eigen_identity(self):
        """Pre-refresh placeholder with the post-refresh pytree structure:
        identity bases and a unit diagonal (an identity preconditioner)."""
        z = self.init_factors()

        def basis(arr, kind):
            if kind == "diag":
                return None
            return arr + jnp.eye(arr.shape[-1], dtype=jnp.float32)

        m = self.meta
        diag_shape = (*self.lead, m.a_dim, m.g_dim)
        return {"qa": basis(z["a"], m.a_kind), "qg": basis(z["g"], m.g_kind),
                "s": jnp.ones(diag_shape, jnp.float32),
                "damp": jnp.zeros(diag_shape, jnp.float32)}

    def eigen_state_multi(self, fac, gammas):
        """Candidate-stacked eigen states (gamma sweep) from one eigh."""
        return INV.eigen_pair_multi(self.meta, fac["a"], fac["g"], gammas)

    def rescale_step(self, eig, grad, eps):
        """Per-step second-moment update ``s ← εs + (1−ε)(Q_Aᵀ ∇ Q_G)²``."""
        return INV.eigen_rescale(self.meta, eig, grad, eps)

    def precondition_eigen(self, eig, v):
        """``U = Q_A [ (Q_Aᵀ V Q_G) / (s + damp) ] Q_Gᵀ``; v shaped like W."""
        self._route("rotate_rescale", False)
        return INV.apply_eigen(self.meta, eig, v)

    def ihvp(self, eig, v):
        """Inverse-Hessian-vector product against this block's damped
        Kronecker Fisher — the eigen apply, exposed under the name the
        influence service uses (``curvature/ihvp.py``)."""
        return self.precondition_eigen(eig, v)

    def ihvp_batched(self, eig, vs):
        """Batched iHVP over a stack of queries (leading ``N`` axis).

        The explicit outer vmap is load-bearing: subclasses' internal
        stacked-layer vmaps close over *all* args, so mapping only ``vs``
        here keeps the shared eigen state un-batched while the Pallas
        ``rotate_rescale`` route rides underneath unchanged."""
        return jax.vmap(lambda v: self.precondition_eigen(eig, v))(vs)

    def eigen_specs(self, mesh) -> Dict[str, Any]:
        """Storage shardings for the eigen state: bases shard like their
        factors; the eigenbasis diagonals shard their d_in axis over `data`
        like the weight (no gathers in the rotate/rescale apply)."""
        from jax.sharding import PartitionSpec as P
        from repro.utils.sharding import pick_shard
        m = self.meta
        fs = self.factor_specs(mesh)
        lead = []
        if m.n_stack:
            lead.append(None)
        if m.n_expert:
            lead.append(pick_shard(m.n_expert, mesh, "model"))
        diag = P(*lead, pick_shard(m.a_dim, mesh, "data"), None)
        return {"qa": None if m.a_kind == "diag" else fs["a"],
                "qg": None if m.g_kind == "diag" else fs["g"],
                "s": diag, "damp": diag}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, List[Type[CurvatureBlock]]] = {}


def register(cls: Type[CurvatureBlock]) -> Type[CurvatureBlock]:
    """Class decorator: file ``cls`` under every kind it serves."""
    for kind in cls.kinds:
        lst = _REGISTRY.setdefault(kind, [])
        lst.append(cls)
        lst.sort(key=lambda c: -c.priority)
    return cls


def registered(kind: str) -> List[Type[CurvatureBlock]]:
    return list(_REGISTRY.get(kind, ()))


def resolve(meta: LayerMeta) -> Type[CurvatureBlock]:
    for cls in _REGISTRY.get(meta.kind, ()):
        if cls.handles(meta):
            return cls
    raise KeyError(f"no curvature block registered for kind={meta.kind!r} "
                   f"(layer {meta.name!r}); known kinds: {sorted(_REGISTRY)}")


def build_blocks(metas: Dict[str, LayerMeta], cfg) -> Dict[str, CurvatureBlock]:
    """One resolved block instance per tagged layer."""
    return {name: resolve(m)(m, cfg) for name, m in metas.items()}


def route_counts(blocks) -> Dict[str, Dict[str, int]]:
    """``{op: {"pallas": n, "einsum": m}}`` over the blocks that traced
    ``op`` — how many took the Pallas kernel and how many declined to the
    einsum path (ragged widths, stacked records, ``kernel_backend="xla"``)."""
    out: Dict[str, Dict[str, int]] = {}
    for blk in blocks.values():
        for op, route in blk.routes.items():
            c = out.setdefault(op, {"pallas": 0, "einsum": 0})
            c[route] += 1
    return dict(sorted(out.items()))
