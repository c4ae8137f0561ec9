"""K-FAC as a staged gradient-transformation pipeline (paper Algorithm 2).

Two layers:

:class:`KFACEngine`
    The jit-able stage functions, one per paper concern.  Each is a pure
    ``state -> state`` (plus grads/params/batch) map over the typed
    :class:`~repro.core.transform.KFACState`:

      ``stats_grads``       every step: one forward, two backwards (true-
                            label gradients + model-sampled g statistics),
                            running factor update (S5).
      ``refresh_inverses``  every T3 steps (and k<=3): damped structured
                            inverses (S4.2/S6.3); ``refresh_multi`` the
                            stacked gamma-candidate set (S6.6);
                            ``refresh_subset`` the staggered variant.
      ``rescale_step``      eigen mode only, every step: EKFAC second-
                            moment diagonal in the amortized eigenbases
                            (George et al. 1806.03884).
      ``apply_update``      every step: preconditioning fused with the
                            exact-F re-scaling + momentum 2x2 solve
                            (S6.4/S7) and candidate selection by M(delta).
      ``lambda_step``       every T1 steps: reduction ratio rho + LM rule
                            (S6.5).
      ``guard``             ends every update program: a non-finite
                            update keeps the old params and ``reject``'s
                            λ and momentum, flagged in its metrics.

    Keeping the stages separate (no lax.cond megakernel) keeps the
    per-step HLO — and hence the roofline accounting — honest; the dry-run
    and distributed tests lower them individually via ``Optimizer.engine``.

:func:`kfac`
    Assembles the stages into a trainer-facing
    ``Optimizer(init, update, reject, state_shardings)``: ``update(None,
    state, params, batch, rng)`` runs one full optimizer step, scheduling
    the amortized stages (T1/T2/T3, warmup, staggered refresh,
    stats_period) off the step counter in the state.  The schedule lives
    here — ``Trainer`` no longer hard-codes the five-call K-FAC
    choreography and can race any :class:`Optimizer` (see
    ``repro.optimizers.baselines``).

Per-layer behavior (factor layout, statistics, damped inverses,
preconditioner apply) lives in a ``CurvatureBlock`` from ``core/blocks`` —
the engine only iterates blocks polymorphically.  The shared numerics sit
in ``core/factors.py`` (S3/S5), ``core/inverse.py`` (S4.2/S6.3),
``core/tridiag.py`` (S4.3/App B), ``core/fisher.py`` (S6.4/App C) and
``core/damping.py`` (S6.5/S6.6).  With ``KFACConfig.kernel_backend ==
"pallas"``, dense blocks route their factor accumulation and two-sided
apply through the Pallas kernels in ``repro.kernels``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import KFACConfig
from repro.core import damping as D
from repro.core import factors as F
from repro.core import fisher as FI
from repro.core.blocks import TridiagChain, build_blocks
from repro.core.transform import KFACState, Optimizer
from repro.utils import tree as T


def _path_tuple(keypath) -> tuple:
    out = []
    for k in keypath:
        if hasattr(k, "key"):
            out.append(k.key)
        elif hasattr(k, "idx"):
            out.append(k.idx)
        else:
            out.append(str(k))
    return tuple(out)


class KFACEngine:
    """model must provide: metas, loss(params, probes, batch, rng, mode),
    probe_shapes(batch), plus `hidden`/`head_weight` (LM) or `logits` (MLP)."""

    def __init__(self, model, cfg: KFACConfig, mesh=None,
                 family: str = "categorical"):
        if cfg.kernel_backend not in ("xla", "pallas"):
            raise ValueError(f"unknown kernel_backend {cfg.kernel_backend!r}"
                             " (expected 'xla' or 'pallas')")
        if cfg.inv_mode not in ("blkdiag", "tridiag", "eigen"):
            raise ValueError(f"unknown inv_mode {cfg.inv_mode!r}"
                             " (expected 'blkdiag', 'tridiag' or 'eigen')")
        if cfg.refresh_mode not in ("serial", "staggered", "sharded",
                                    "overlap"):
            raise ValueError(
                f"unknown refresh_mode {cfg.refresh_mode!r} (expected "
                "'serial', 'staggered', 'sharded' or 'overlap')")
        if cfg.autotune not in ("off", "cache", "force"):
            raise ValueError(f"unknown autotune {cfg.autotune!r}"
                             " (expected 'off', 'cache' or 'force')")
        # legacy knob: staggered_inverse=True was the only way to ask for
        # the round-robin refresh before refresh_mode existed
        self.refresh_mode = ("staggered"
                             if cfg.refresh_mode == "serial"
                             and cfg.staggered_inverse
                             else cfg.refresh_mode)
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.family = family
        self.metas = model.metas
        self.is_lm = hasattr(model, "hidden")
        self.tagged = {m.param_path for m in self.metas.values()}
        self.tridiag = (cfg.inv_mode == "tridiag"
                        and hasattr(model, "layer_order"))
        self.eigen = cfg.inv_mode == "eigen"
        self.blocks = build_blocks(self.metas, cfg)
        self.chain = TridiagChain(model, cfg) if self.tridiag else None
        self._probe_shapes = None
        # backward-pass fusion of the factor statistics (core/fused): the
        # A-side contractions ride the forward via the model's contract_map
        # hooks and the G side via the custom-VJP gg-probes.  Installing the
        # hooks mutates the model's contract maps — models are built per
        # engine in practice; a tridiag engine must not share a model a
        # fused engine already wired.
        self.fused = bool(cfg.fused_stats) and not self.tridiag
        self.fused_names = set()
        if self.fused:
            from repro.core import fused as FU
            from repro.kernels.backend import resolve_interpret
            cmap = getattr(model, "contract_map", None)
            gmap = getattr(model, "gcontract_map", None)
            if cmap is not None and gmap is not None:
                interpret = resolve_interpret(None)
                self.fused_names = {n for n, m in self.metas.items()
                                    if FU.fused_eligible(m)}
                for n in sorted(self.fused_names):
                    m = self.metas[n]
                    if n not in cmap:
                        mk = (FU.conv_a_contract if m.kind == "conv"
                              else FU.dense_a_contract)
                        cmap[n] = mk(m, cfg.kernel_backend, interpret,
                                     cfg.autotune)
                    gmap[n] = FU.g_contract(m, cfg.kernel_backend,
                                            interpret, cfg.autotune)

    # ------------------------------------------------------------------
    def n_tokens(self, batch) -> int:
        if not self.is_lm:
            return int(batch["x"].shape[0])
        b, t = batch["tokens"].shape
        if self.model.cfg.frontend == "patch":
            t += self.model.cfg.frontend_tokens
        return int(b * t)

    def _probes(self, batch):
        if self._probe_shapes is None:
            self._probe_shapes = self.model.probe_shapes(
                jax.eval_shape(lambda b: b, batch))
        probes = self.model.make_probes(self._probe_shapes)
        if self.fused_names:
            # fused layers swap the (N, d_out) zero probe for the tiny
            # {"gg": (d_out, d_out)} probe whose VJP cotangent is the
            # already-contracted second moment (core/fused.apply_gprobe)
            from repro.core import fused as FU
            from repro.kernels.backend import resolve_interpret
            for n in self.fused_names:
                probes[n] = FU.gg_probe(self.metas[n])
        return probes

    def _is_tagged(self, keypath) -> bool:
        return _path_tuple(keypath) in self.tagged

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, params, batch) -> KFACState:
        factors = {name: blk.init_factors()
                   for name, blk in self.blocks.items()}
        if self.chain is not None:
            factors[TridiagChain.CROSS] = self.chain.init_factors()
        diag = jax.tree_util.tree_map_with_path(
            lambda kp, x: (jnp.zeros((0,), jnp.float32) if self._is_tagged(kp)
                           else jnp.zeros_like(x, jnp.float32)), params)
        inv = self._identity_inverses()
        return KFACState(
            step=jnp.int32(0),
            k_stats=jnp.int32(0),
            lam=jnp.float32(self.cfg.lambda_init),
            gamma=jnp.float32(math.sqrt(self.cfg.lambda_init + self.cfg.eta)),
            factors=factors,
            inv=inv,
            diag=diag,
            delta0=T.tree_zeros_like(T.tree_cast(params, jnp.float32)),
            m_delta=jnp.float32(-1.0),
            loss_prev=jnp.float32(0.0),
            staleness=jnp.int32(0),
            # overlap mode double-buffers the inverses; the other refresh
            # modes keep the slot empty (None) and pay no extra state
            inv_pending=(inv if self.refresh_mode == "overlap" else None),
        )

    def _identity_inverses(self):
        if self.eigen:
            return {name: blk.eigen_identity()
                    for name, blk in self.blocks.items()}
        out = {name: blk.identity_inverse()
               for name, blk in self.blocks.items()}
        if self.chain is not None:
            out[TridiagChain.TRI] = self.chain.identity_inverse()
        return out

    def state_shardings(self, state_abs: KFACState, param_shardings, mesh):
        """NamedSharding tree (a KFACState) for the optimizer state.

        Factor/inverse storage is FSDP-spread over `data` and stack/expert/
        block dims over `model` (see CurvatureBlock.factor_specs); diag &
        momentum follow the parameter shardings; scalars replicate."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        fs = {name: blk.factor_specs(mesh) for name, blk in self.blocks.items()}
        fac_sh = {name: {"a": NamedSharding(mesh, fs[name]["a"]),
                         "g": NamedSharding(mesh, fs[name]["g"])}
                  for name in self.metas}
        if self.eigen:
            # eigenbases shard like their factors; the eigenbasis diagonals
            # like the weight (None entries pair with the identity bases)
            inv_sh = {
                name: {k: (None if spec is None else NamedSharding(mesh, spec))
                       for k, spec in blk.eigen_specs(mesh).items()}
                for name, blk in self.blocks.items()}
        else:
            inv_sh = {name: {"a_inv": fac_sh[name]["a"],
                             "g_inv": fac_sh[name]["g"]}
                      for name in self.metas}
        if self.chain is not None:
            cross, tri = TridiagChain.CROSS, TridiagChain.TRI
            fac_sh[cross] = jax.tree.map(lambda _: rep,
                                         state_abs.factors[cross])
            inv_sh[tri] = jax.tree.map(lambda _: rep,
                                       state_abs.inv[tri])
        diag_sh = jax.tree.map(
            lambda leaf, sh: rep if leaf.size == 0 else sh,
            state_abs.diag, param_shardings)
        return KFACState(
            step=rep, k_stats=rep, lam=rep, gamma=rep,
            factors=fac_sh, inv=inv_sh, diag=diag_sh,
            delta0=param_shardings,
            m_delta=rep, loss_prev=rep,
            staleness=rep,
            # the pending buffer shards exactly like the live inverses
            inv_pending=(inv_sh if state_abs.inv_pending is not None
                         else None),
        )

    # ------------------------------------------------------------------
    # stats + grads (paper tasks 1–4): a full-batch gradient pass, plus a
    # tau1-subsampled model-sampled-target pass for the factor statistics.
    # The stats pass differentiates only w.r.t. the probes, so its backward
    # is the cheap activation-only chain (no dW products — task 3's C1 cost).
    # ------------------------------------------------------------------
    def _sub_batch(self, batch):
        stride = max(1, round(1.0 / self.cfg.tau1))
        if stride == 1:
            return batch
        # strided slice stays aligned with the batch sharding
        return jax.tree.map(lambda x: x[::stride], batch)

    def _constrain_grads(self, grads):
        """Pin gradients to the parameter storage layout so partial-sum
        reductions lower as reduce-scatters into the FSDP shards rather than
        full all-reduces."""
        if self.mesh is None or not hasattr(self.model, "param_shardings"):
            return grads
        return jax.lax.with_sharding_constraint(
            grads, self.model.param_shardings())

    def stats_grads(self, state: KFACState, params, batch, rng):
        # ---- pass 1: gradients on the full batch (plain mode) ----
        def f1(p):
            (lt, _), aux = self.model.loss(p, None, batch, rng, mode="plain")
            return lt, aux["metrics"]

        (lt, metrics1), grads = jax.value_and_grad(f1, has_aux=True)(params)
        grads = self._constrain_grads(grads)

        # ---- pass 2: tau1-subsampled statistics with sampled targets ----
        sub = self._sub_batch(batch)
        probes = self._probes(sub)
        n = self.n_tokens(sub)
        rng2 = jax.random.fold_in(rng, 1)

        def f2(pr):
            (_, ls), aux = self.model.loss(params, pr, sub, rng2,
                                           mode="collect")
            return ls, aux

        ls, vjp_fn, aux = jax.vjp(f2, probes, has_aux=True)
        (gprobes,) = vjp_fn(jnp.float32(1.0))
        recs = aux["recs"]

        # each block folds its own contribution into the decayed running
        # factors (dense blocks may fuse this through the Pallas kernel)
        k = state.k_stats + 1
        eps = F.decay_eps(k, self.cfg.decay_cap)
        factors = {
            name: blk.update_factors(state.factors[name], recs.get(name),
                                     gprobes.get(name), sub, n, eps)
            for name, blk in self.blocks.items()}
        if self.chain is not None:
            cross = TridiagChain.CROSS
            factors[cross] = self.chain.update_factors(
                state.factors[cross], recs, gprobes, sub, n, eps)

        # diagonal running curvature for untagged (elementwise) params —
        # squared gradients (these cover <1% of parameters; the tagged
        # weights use the proper Kronecker blocks)
        diag_new = jax.tree_util.tree_map_with_path(
            lambda kp, g, old: (old if self._is_tagged(kp)
                                else eps * old
                                + (1 - eps) * jnp.square(g.astype(jnp.float32))),
            grads, state.diag)

        state = state.replace(factors=factors, diag=diag_new, k_stats=k,
                              loss_prev=lt)
        metrics = dict(metrics1, loss_sampled=ls)
        return state, grads, metrics

    # ------------------------------------------------------------------
    # inverses
    # ------------------------------------------------------------------
    def _inverses_for(self, factors, gamma, prev=None):
        cfg = self.cfg
        if self.eigen:
            return {name: blk.eigen_state(factors[name], gamma)
                    for name, blk in self.blocks.items()}
        out = {}
        for name, blk in self.blocks.items():
            out[name] = blk.damped_inverse(
                factors[name], gamma,
                method=cfg.inverse_method, iters=cfg.ns_iters,
                prev=None if prev is None else prev.get(name))
        if self.chain is not None:
            out[TridiagChain.TRI] = self.chain.damped_inverse(factors, gamma)
        return out

    def refresh_inverses(self, state: KFACState, hot: bool = False):
        prev = state.inv if (hot and self.cfg.inverse_method == "ns") else None
        inv = self._inverses_for(state.factors, state.gamma, prev)
        return state.replace(inv=inv)

    def refresh_subset(self, state: KFACState, names, hot: bool = True):
        """Staggered refresh (beyond-paper, DESIGN §3): recompute only the
        named layer blocks — the trainer round-robins so 1/T3 of the d³ work
        lands on each step instead of spiking every T3 steps."""
        cfg = self.cfg
        inv = dict(state.inv)
        if self.eigen:
            for name in names:
                inv[name] = self.blocks[name].eigen_state(
                    state.factors[name], state.gamma)
            return state.replace(inv=inv)
        prev = state.inv if cfg.inverse_method == "ns" and hot else None
        for name in names:
            inv[name] = self.blocks[name].damped_inverse(
                state.factors[name], state.gamma,
                method=cfg.inverse_method,
                iters=cfg.ns_hot_iters if hot else cfg.ns_iters,
                prev=None if prev is None else prev.get(name))
        return state.replace(inv=inv)

    def rescale_step(self, state: KFACState, grads):
        """Eigen mode, every step: re-estimate each block's eigenbasis
        second-moment diagonal from the current gradient (EKFAC's cheap
        half — the bases stay on the amortized T3 schedule).  No-op in the
        other inv_modes."""
        if not self.eigen:
            return state
        eps = jnp.float32(self.cfg.eigen_decay)
        inv = dict(state.inv)
        for name, blk in self.blocks.items():
            v = T.get_path(grads, blk.meta.param_path)
            inv[name] = blk.rescale_step(inv[name], v, eps)
        return state.replace(inv=inv)

    def stagger_groups(self):
        """Partition layer names into T3 staggered refresh groups, balanced
        by the d³ inversion cost model (repro.distributed.plan) instead of
        the old declaration-order round-robin — the per-step refresh work
        is even regardless of how layer sizes interleave."""
        from repro.distributed.plan import build_plan
        return build_plan(self.blocks, max(1, self.cfg.t3)).groups()

    def grads_only(self, state: KFACState, params, batch, rng):
        """Gradient pass without the statistics pass (straggler/budget mode
        via KFACConfig.stats_period)."""
        def f1(p):
            (lt, _), aux = self.model.loss(p, None, batch, rng, mode="plain")
            return lt, aux["metrics"]

        (lt, metrics), grads = jax.value_and_grad(f1, has_aux=True)(params)
        return state.replace(loss_prev=lt), grads, metrics

    def refresh_multi(self, state: KFACState):
        """Stacked inverses for the 3 gamma candidates (S6.6), via vmap.

        Eigen mode shares one eigendecomposition across the candidates —
        the bases are gamma-independent; only the damp diagonal varies."""
        gammas = D.gamma_candidates(state.gamma, self._omega2())
        if self.eigen:
            inv3 = {name: blk.eigen_state_multi(state.factors[name],
                                                gammas)
                    for name, blk in self.blocks.items()}
            return gammas, inv3
        inv3 = jax.vmap(lambda g: self._inverses_for(state.factors, g))(
            gammas)
        return gammas, inv3

    def _omega1(self):
        return float(self.cfg.omega1_base ** self.cfg.t1)

    def _omega2(self):
        return float(math.sqrt(self.cfg.omega2_base) ** self.cfg.t2)

    # ------------------------------------------------------------------
    # preconditioning
    # ------------------------------------------------------------------
    def _precondition(self, grads_reg, inv, state: KFACState):
        lam_eta = state.lam + self.cfg.eta
        # untagged params: diagonal curvature
        out = jax.tree_util.tree_map_with_path(
            lambda kp, g, d: (g if self._is_tagged(kp)
                              else g / (d + lam_eta)),
            grads_reg, state.diag)
        if self.chain is not None:
            vs = {name: T.get_path(grads_reg, self.metas[name].param_path)
                  for name in self.model.layer_order}
            us = self.chain.precondition(inv[TridiagChain.TRI], vs)
            for name, u in us.items():
                out = T.set_path(out, self.metas[name].param_path, u)
        else:
            for name, blk in self.blocks.items():
                v = T.get_path(grads_reg, blk.meta.param_path)
                u = (blk.precondition_eigen(inv[name], v) if self.eigen
                     else blk.precondition(inv[name], v))
                out = T.set_path(out, blk.meta.param_path, u)
        return T.tree_scale(out, -1.0)

    # ------------------------------------------------------------------
    # update: precondition fused with rescale + momentum + candidate select
    # ------------------------------------------------------------------
    def apply_update(self, state: KFACState, params, grads, batch, rng, *,
                     cand_inv: Optional[List] = None, gammas=None,
                     loss_now=None):
        """cand_inv: list of inverse pytrees (candidates); default state.inv.
        Returns (params', state', metrics)."""
        cfg = self.cfg
        invs = cand_inv if cand_inv is not None else [state.inv]
        nc = len(invs)
        grads_reg = T.tree_axpy(cfg.eta, T.tree_cast(params, jnp.float32),
                                T.tree_cast(grads, jnp.float32))

        deltas = [self._precondition(grads_reg, inv, state) for inv in invs]
        use_mom = cfg.use_momentum
        tangents = deltas + ([state.delta0] if use_mom else [])
        m = len(tangents)

        lam_eta = state.lam + cfg.eta
        if cfg.use_rescale:
            if self.is_lm:
                q = FI.quad_lm(self.model, params, batch, tangents)
            else:
                q = FI.quad_logits(
                    lambda p: self.model.logits(p, batch["x"]),
                    params, batch, tangents, self.family)
            dots = jnp.array([[T.tree_dot(tangents[i], tangents[j])
                               for j in range(m)] for i in range(m)])
            q = q + lam_eta * dots
            b = jnp.array([T.tree_dot(grads_reg, t) for t in tangents])

            alphas, mus, ms = [], [], []
            for c in range(nc):
                if use_mom:
                    idx = jnp.array([c, m - 1])
                    q2 = q[jnp.ix_(idx, idx)] + 1e-20 * jnp.eye(2)
                    b2 = b[idx]
                    x = -jnp.linalg.solve(q2, b2)
                    mval = 0.5 * x @ q2 @ x + b2 @ x
                    alphas.append(x[0]); mus.append(x[1]); ms.append(mval)
                else:
                    a = -b[c] / jnp.maximum(q[c, c], 1e-20)
                    alphas.append(a); mus.append(jnp.float32(0.0))
                    ms.append(0.5 * a * a * q[c, c] + a * b[c])
            alphas = jnp.stack(alphas); mus = jnp.stack(mus)
            ms = jnp.stack(ms)
            c_star = jnp.argmin(ms)
            alpha = alphas[c_star]
            mu = mus[c_star]
            m_delta = ms[c_star]
        else:
            alpha = jnp.float32(cfg.fixed_lr)
            mu = jnp.float32(0.0)
            c_star = jnp.int32(0)
            m_delta = jnp.float32(-1.0)

        # select the winning candidate's delta (and inverses / gamma) by
        # indexing the stacked candidates — one gather per leaf
        if nc == 1:
            delta_sel = deltas[0]
            inv_sel = invs[0]
            gamma_new = state.gamma
        else:
            delta_sel = jax.tree.map(
                lambda *xs: jnp.take(jnp.stack(xs), c_star, axis=0), *deltas)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *invs)
            inv_sel = jax.tree.map(lambda x: jnp.take(x, c_star, axis=0),
                                   stacked)
            gamma_new = gammas[c_star]

        delta = T.tree_scale(delta_sel, alpha)
        if use_mom:
            delta = T.tree_axpy(mu, state.delta0, delta)
        new_params = jax.tree.map(
            lambda p, d: (p + d.astype(p.dtype)), params, delta)

        state = state.replace(step=state.step + 1, delta0=delta,
                              m_delta=m_delta, inv=inv_sel, gamma=gamma_new)
        metrics = {
            "alpha": alpha, "mu": mu, "m_delta": m_delta,
            "gamma": gamma_new, "lam": state.lam,
            "grad_norm": jnp.sqrt(T.tree_sqnorm(grads_reg)),
            "delta_norm": jnp.sqrt(T.tree_sqnorm(delta)),
        }
        return new_params, state, metrics

    # ------------------------------------------------------------------
    # fused fixed-lr update chain: precondition + momentum + global clip
    # ------------------------------------------------------------------
    def apply_update_fused(self, state: KFACState, params, grads, batch,
                           rng, *, inv_override=None, gamma_override=None):
        """The ``use_rescale=False`` path as ONE fused stage: per block,
        ``D = −lr·(Ā⁻¹ V G⁻¹) + μ·M`` together with ``Σ D²`` comes out of a
        single ``CurvatureBlock.precond_momentum`` call (Pallas blocks serve
        it with the fused ``update_chain`` kernel), so the global-norm clip
        folds into the parameter apply without ever re-reading the update.

        With ``fixed_momentum == 0``, ``clip_delta_norm == 0`` and
        ``kl_clip == 0`` this is bitwise the legacy three-stage path.  On T2 candidate steps the
        caller passes candidate 0's inverses/gamma (the legacy fixed-lr
        ``c_star = 0`` selection).  Returns (params', state', metrics)."""
        cfg = self.cfg
        inv = inv_override if inv_override is not None else state.inv
        gamma_new = (gamma_override if gamma_override is not None
                     else state.gamma)
        lam_eta = state.lam + cfg.eta
        alpha = -jnp.float32(cfg.fixed_lr)
        mu = jnp.float32(cfg.fixed_momentum)
        grads_reg = T.tree_axpy(cfg.eta, T.tree_cast(params, jnp.float32),
                                T.tree_cast(grads, jnp.float32))
        sqs = []

        # untagged params: diagonal curvature, axpy'd in the same traversal
        def leaf(kp, g, dd, mom):
            if self._is_tagged(kp):
                return mom            # overwritten by the block loop below
            d = alpha * (g / (dd + lam_eta)) + mu * mom
            sqs.append(jnp.sum(d * d))
            return d

        vel = jax.tree_util.tree_map_with_path(leaf, grads_reg, state.diag,
                                               state.delta0)
        if self.chain is not None:
            vs = {name: T.get_path(grads_reg, self.metas[name].param_path)
                  for name in self.model.layer_order}
            us = self.chain.precondition(inv[TridiagChain.TRI], vs)
            for name, blk in self.blocks.items():
                path = blk.meta.param_path
                u = us.get(name, T.get_path(grads_reg, path))
                d = (alpha * u.astype(jnp.float32)
                     + mu * T.get_path(state.delta0, path))
                sqs.append(jnp.sum(d * d))
                vel = T.set_path(vel, path, d)
        else:
            for name, blk in self.blocks.items():
                path = blk.meta.param_path
                d, sq = blk.precond_momentum(
                    inv[name], T.get_path(grads_reg, path),
                    T.get_path(state.delta0, path), alpha, mu,
                    eigen=self.eigen)
                sqs.append(sq)
                vel = T.set_path(vel, path, d)

        norm = jnp.sqrt(sum(sqs) if sqs else jnp.float32(0.0))
        factor = jnp.float32(1.0)
        if cfg.kl_clip > 0:
            # trust region on the Fisher quadratic of the applied step:
            # vel already carries -lr, so |velᵀ∇| ≈ lr²·ΔᵀFΔ and
            # ν = min(1, sqrt(max_kl / |velᵀ∇|))  (transform.with_kl_clip)
            quad = jnp.abs(T.tree_dot(vel, grads_reg))
            factor = factor * jnp.minimum(
                jnp.float32(1.0),
                jnp.sqrt(cfg.kl_clip / jnp.maximum(quad, 1e-20)))
        if cfg.clip_delta_norm > 0:
            factor = factor * jnp.minimum(
                jnp.float32(1.0),
                cfg.clip_delta_norm / jnp.maximum(norm, 1e-20))
        if cfg.kl_clip > 0 or cfg.clip_delta_norm > 0:
            new_params = jax.tree.map(
                lambda p, d: p + (factor * d).astype(p.dtype), params, vel)
            delta_norm = factor * norm
        else:
            new_params = jax.tree.map(
                lambda p, d: p + d.astype(p.dtype), params, vel)
            delta_norm = norm

        # delta0 keeps the PRE-clip velocity (with_momentum semantics)
        state = state.replace(step=state.step + 1, delta0=vel,
                              m_delta=jnp.float32(-1.0), inv=inv,
                              gamma=gamma_new)
        metrics = {
            "alpha": jnp.float32(cfg.fixed_lr), "mu": mu,
            "m_delta": jnp.float32(-1.0), "gamma": gamma_new,
            "lam": state.lam,
            "grad_norm": jnp.sqrt(T.tree_sqnorm(grads_reg)),
            "delta_norm": delta_norm,
        }
        if cfg.kl_clip > 0 or cfg.clip_delta_norm > 0:
            # the applied clip factor nu (1.0 = no clipping bit).  Only
            # added when a clip is configured so the default jitted
            # program's output structure is unchanged.
            metrics["nu"] = factor
        return new_params, state, metrics

    # ------------------------------------------------------------------
    # lambda adaptation (S6.5)
    # ------------------------------------------------------------------
    def lambda_step(self, state: KFACState, new_params, batch, rng):
        (l_new, _), _ = self.model.loss(new_params, None, batch, rng,
                                        mode="plain")
        rho = (l_new - state.loss_prev) / jnp.minimum(
            state.m_delta, -1e-20)
        lam = D.lambda_update(state.lam, rho, self._omega1())
        return state.replace(lam=lam), rho

    # ------------------------------------------------------------------
    # non-finite guard, on the device
    # ------------------------------------------------------------------
    def reject(self, state: KFACState) -> KFACState:
        """A non-finite update was skipped: raise damping, drop momentum."""
        return state.replace(lam=state.lam * 4.0,
                             delta0=T.tree_zeros_like(state.delta0))

    def guard(self, params, new_params, state: KFACState, metrics):
        """Non-finite guard, traced into the program that writes the
        update: ``finite`` is one reduction over the new params and the
        update's norm; a non-finite update keeps the old params and the
        rejected λ and momentum (:meth:`reject`).  Only those leaves are
        selected; the rest of the state is the update's.
        ``metrics["finite"]`` carries the flag."""
        finite = (T.tree_isfinite(new_params)
                  & jnp.isfinite(metrics["delta_norm"]))

        def keep(new, old):
            return jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                new, old)

        rejected = self.reject(state)
        state = state.replace(lam=keep(state.lam, rejected.lam),
                              delta0=keep(state.delta0, rejected.delta0))
        return keep(new_params, params), state, dict(metrics, finite=finite)

    def guarded_lambda_step(self, state: KFACState, new_params, batch, rng,
                            finite, lam):
        """``lambda_step`` after a guarded update: the rule runs from the λ
        the update saw (``lam``) at the params the guard kept, and a
        rejected update's :meth:`reject` comes after it (the rule clips,
        so the order matters)."""
        state, rho = self.lambda_step(state.replace(lam=lam), new_params,
                                      batch, rng)
        lam = jnp.where(finite, state.lam, self.reject(state).lam)
        return state.replace(lam=lam), rho


# ---------------------------------------------------------------------------
# the pipeline: stages + schedule -> Optimizer(init, update, reject)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepContext:
    """Mutable per-step scratch the stages thread their work through."""

    step: int
    warmup: bool
    state: KFACState
    params: Any
    batch: Any
    rng: Any
    grads: Any = None
    new_params: Any = None
    candidates: Any = None          # (gammas, stacked inv3) on T2 steps
    metrics: dict = dataclasses.field(default_factory=dict)


class Stage(NamedTuple):
    name: str
    run: Callable[[StepContext], None]


def _jit_named(name: str, fn: Callable) -> Callable:
    """``jax.jit`` of ``fn`` under ``name``, so that its module reads
    ``jit_<name>`` in a device trace or compile log, not ``jit__lambda``;
    the program is otherwise the one ``jax.jit(fn)`` builds."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class KFACPipeline:
    """Drives one optimizer step as an ordered list of named stages.

    Each stage owns its own schedule predicate (read off the concrete step
    counter) and calls a *jitted* engine stage — the composition is
    host-level, so the per-stage HLO stays separate (roofline honesty) and
    the step sequence is bit-identical to driving the engine stages by
    hand, which ``tests/test_transform.py`` pins per ``inv_mode``.
    """

    def __init__(self, engine: KFACEngine, obs=None):
        from repro import obs as obs_mod
        self.engine = eng = engine
        cfg = eng.cfg
        # telemetry (repro.obs): obs=None reads the engine's cfg.obs; pass
        # a shared Obs to land pipeline events in the same log as the
        # trainer's.  Disabled, the spans below are no-op context managers
        # (no clocks, no block_until_ready) and the jitted stages are
        # byte-identical — pinned by tests/test_obs.py.
        self.obs = obs_mod.from_config(obs if obs is not None else cfg.obs)
        self._start: Optional[int] = None
        self._stats = jax.jit(eng.stats_grads)
        self._grads_only = jax.jit(eng.grads_only)
        self._rescale = jax.jit(eng.rescale_step) if eng.eigen else None
        self._refresh = _jit_named(
            "kfac_refresh", lambda s: eng.refresh_inverses(s, hot=True))
        self._refresh_sub = {
            i: _jit_named("kfac_refresh_group",
                          lambda s, ns=tuple(g): eng.refresh_subset(s, ns))
            for i, g in enumerate(eng.stagger_groups())} \
            if eng.refresh_mode == "staggered" else None
        # distributed curvature service (repro.distributed): the sharded
        # block-parallel refresh, plus the async double-buffer controller
        self._refresh_sharded = None
        self._overlap = None
        if eng.refresh_mode in ("sharded", "overlap"):
            from repro.distributed.overlap import OverlapController
            from repro.distributed.refresh import build_sharded_refresh
            self._refresh_sharded = build_sharded_refresh(eng)
            if eng.refresh_mode == "overlap":
                self._overlap = OverlapController(
                    self._refresh_sharded, bound=max(1, cfg.t3),
                    deterministic=cfg.overlap_deterministic, obs=self.obs)
        self._multi = jax.jit(eng.refresh_multi)
        # every update program ends in the non-finite guard
        # (KFACEngine.guard): its metrics carry the device flag "finite"
        if cfg.use_rescale:
            self._update = _jit_named(
                "kfac_update",
                lambda s, p, g, b, r: eng.guard(
                    p, *eng.apply_update(s, p, g, b, r)))
            self._update3 = _jit_named(
                "kfac_update3",
                lambda s, p, g, b, r, gs, i3: eng.guard(p, *eng.apply_update(
                    s, p, g, b, r,
                    cand_inv=[jax.tree.map(lambda x: x[c], i3)
                              for c in range(3)],
                    gammas=gs)))
            # precondition is fused into the quadratic-model stage: the
            # M(delta) solve needs every candidate's preconditioned delta
            # and the exact-F products in one HLO (S6.4/S6.6)
            update_stage = Stage("precondition+quadratic_model_lr_momentum",
                                 self._stage_quadratic)
        else:
            # fixed-lr path: precondition + momentum + global-norm clip as
            # one fused stage (docs/optimizer_api.md "stage map"); on T2
            # steps the gamma sweep keeps candidate 0 (legacy c_star=0)
            self._update = _jit_named(
                "kfac_update",
                lambda s, p, g, b, r: eng.guard(
                    p, *eng.apply_update_fused(s, p, g, b, r)))
            self._update3 = _jit_named(
                "kfac_update3",
                lambda s, p, g, b, r, gs, i3: eng.guard(
                    p, *eng.apply_update_fused(
                        s, p, g, b, r,
                        inv_override=jax.tree.map(lambda x: x[0], i3),
                        gamma_override=gs[0])))
            update_stage = Stage("fused_precondition_momentum_clip",
                                 self._stage_quadratic)
        self._lambda = _jit_named(
            "lambda_step", lambda s, p, b, r, f, lam: eng.guarded_lambda_step(
                s, p, b, r, f, lam))
        # the step counter is read from the state once per run (the
        # pipeline's one sync site, always counted) and then counted here:
        # _step is the step of the state whose step leaf is _step_leaf
        self._c_read_step = self.obs.counter("train/host_syncs",
                                             {"site": "kfac/read_step"})
        self._step: Optional[int] = None
        self._step_leaf = None
        self.stages = [
            Stage("estimate_stats", self._stage_estimate_stats),
            Stage("scheduled_inverse_refresh", self._stage_refresh),
            Stage("eigen_rescale", self._stage_eigen_rescale),
            update_stage,
            Stage("adapt_lambda", self._stage_adapt_lambda),
        ]

    # -- stages --------------------------------------------------------
    def _stage_estimate_stats(self, ctx: StepContext):
        if ctx.grads is not None:
            raise ValueError(
                "kfac computes its own gradients (the statistics pass "
                "shares the forward with the gradient pass) — call "
                "update(None, state, params, batch, rng)")
        if ctx.step % self.engine.cfg.stats_period == 0:
            ctx.state, ctx.grads, metrics = self._stats(
                ctx.state, ctx.params, ctx.batch, ctx.rng)
        else:
            # stats skipped (straggler/budget mode): grads only
            ctx.state, ctx.grads, metrics = self._grads_only(
                ctx.state, ctx.params, ctx.batch, ctx.rng)
        ctx.metrics.update(metrics)

    def _full_refresh(self, state: KFACState) -> KFACState:
        """Synchronous full refresh via the mode's executor: the serial
        engine stage, or the block-parallel sharded service."""
        sharded = self._refresh_sharded is not None
        mode = "sharded" if sharded else "serial"
        with self.obs.span(f"refresh/{mode}",
                           block=lambda: out.inv) as sp:
            if sharded:
                inv = self._refresh_sharded(state.factors, state.gamma,
                                            state.inv)
                out = state.replace(inv=inv)
            else:
                out = self._refresh(state)
        if self.obs.enabled:
            payload = {"mode": mode, "wall_s": sp.seconds}
            plan = getattr(self._refresh_sharded, "plan", None)
            if plan is not None:
                payload.update(n_shards=plan.n_shards,
                               serial_cost=float(plan.serial_cost()),
                               parallel_cost=float(plan.parallel_cost()))
            self.obs.emit("refresh", **payload)
        return out

    def _stage_refresh(self, ctx: StepContext):
        cfg = self.engine.cfg
        if cfg.t2 > 0 and ctx.step > 0 and ctx.step % cfg.t2 == 0:
            # gamma sweep (S6.6): stacked candidate inverses; selection
            # happens inside the quadratic-model stage
            with self.obs.span("refresh/gamma_sweep",
                               block=lambda: ctx.candidates):
                ctx.candidates = self._multi(ctx.state)
            if self._overlap is not None:
                # the sweep recomputes inverses synchronously from the
                # current factors — an older in-flight buffer must not
                # overwrite them later
                self._overlap.cancel(ctx.step)
                ctx.state = ctx.state.replace(staleness=jnp.int32(0))
        elif self._overlap is not None and not ctx.warmup:
            ctl = self._overlap
            commits0 = ctl.n_commits
            ctx.state = ctl.on_refresh_stage(
                ctx.state, ctx.step, due=(ctx.step % cfg.t3 == 0))
            ctx.metrics["staleness"] = ctx.state.staleness
            if self.obs.enabled and ctl.n_commits > commits0:
                # an async buffer just swapped in: the dispatch->commit
                # wall (+ whether the commit had to block) is the refresh
                self.obs.emit("refresh", mode="overlap",
                              wall_s=ctl.last_refresh_s,
                              forced=ctl.last_forced,
                              staleness=int(ctx.state.staleness),
                              n_cancelled=ctl.n_cancelled)
        elif ctx.warmup:
            ctx.state = self._full_refresh(ctx.state)
        elif self._refresh_sub is not None:
            # staggered: 1/T3 of the layer inverses per step, groups
            # balanced by the d³ cost model
            group = ctx.step % cfg.t3
            with self.obs.span("refresh/staggered",
                               block=lambda: ctx.state.inv) as sp:
                ctx.state = self._refresh_sub[group](ctx.state)
            if self.obs.enabled:
                self.obs.emit("refresh", mode="staggered",
                              wall_s=sp.seconds, group=group)
        elif ctx.step % cfg.t3 == 0:
            ctx.state = self._full_refresh(ctx.state)

    def _stage_eigen_rescale(self, ctx: StepContext):
        if self._rescale is not None and ctx.candidates is None:
            # eigen mode: per-step EKFAC diagonal re-estimation in the
            # (amortized) eigenbases
            ctx.state = self._rescale(ctx.state, ctx.grads)

    def _stage_quadratic(self, ctx: StepContext):
        if ctx.candidates is not None:
            gs, i3 = ctx.candidates
            ctx.new_params, ctx.state, um = self._update3(
                ctx.state, ctx.params, ctx.grads, ctx.batch, ctx.rng, gs, i3)
        else:
            ctx.new_params, ctx.state, um = self._update(
                ctx.state, ctx.params, ctx.grads, ctx.batch, ctx.rng)
        ctx.metrics.update(um)

    def _stage_adapt_lambda(self, ctx: StepContext):
        cfg = self.engine.cfg
        if cfg.t1 > 0 and (ctx.step + 1) % cfg.t1 == 0:
            # rho at the params the update's guard kept (the old ones after
            # a non-finite update); the rule starts from the λ the update
            # saw (its metric), before the guard's reject
            ctx.state, rho = self._lambda(
                ctx.state, ctx.new_params, ctx.batch, ctx.rng,
                ctx.metrics["finite"], ctx.metrics["lam"])
            ctx.metrics["rho"] = rho

    # -- Optimizer protocol --------------------------------------------
    def init(self, params, batch) -> KFACState:
        self._start = None            # new run: re-arm the warmup refreshes
        self._step = self._step_leaf = None   # and re-read the step
        if self._overlap is not None:
            self._overlap.reset()     # drop any in-flight refresh buffer
        return self.engine.init(params, batch)

    def poll(self, state: KFACState) -> KFACState:
        """Trainer end-of-step hook: commit a finished async refresh
        buffer (overlap mode); never blocks, no-op otherwise."""
        if self._overlap is not None and isinstance(state, KFACState):
            return self._overlap.poll(state)
        return state

    def update(self, grads, state: KFACState, params, batch, rng):
        # schedule off the state, not a loop var: the step is read once,
        # then counted on the host for as long as the caller hands back
        # the state this pipeline returned (each update adds 1)
        if state.step is self._step_leaf:
            step = self._step
        else:
            with self.obs.span("kfac/read_step"):
                step = int(state.step)
            self._c_read_step.inc()
        if self._start is None:
            self._start = step
        ctx = StepContext(step=step, warmup=step - self._start < 3,
                          state=state, params=params, batch=batch, rng=rng,
                          grads=grads)
        if not self.obs.enabled:
            for stage in self.stages:
                stage.run(ctx)
            return self._done(ctx)
        # instrumented path: per-stage wall time (host-side, blocking on
        # the stage's outputs at span close — the jitted programs are the
        # same; only the host gains sync points) + one kfac_step event
        stage_s = {}
        for stage in self.stages:
            blk = lambda: [x for x in (ctx.state, ctx.grads,
                                       ctx.new_params) if x is not None]
            with self.obs.span(f"kfac/{stage.name}", block=blk) as sp:
                stage.run(ctx)
            stage_s[stage.name] = sp.seconds
        self.obs.emit("kfac_step", step=step, stages=stage_s)
        return self._done(ctx)

    def _done(self, ctx: StepContext):
        self._step, self._step_leaf = ctx.step + 1, ctx.state.step
        return ctx.new_params, ctx.state, ctx.metrics


def kfac(model=None, cfg: Optional[KFACConfig] = None, mesh=None,
         family: str = "categorical", *,
         engine: Optional[KFACEngine] = None, obs=None) -> Optimizer:
    """Build the K-FAC optimizer pipeline as an ``Optimizer``.

        opt = kfac(model, KFACConfig(...))
        state = opt.init(params, batch)
        new_params, state, metrics = opt.update(None, state, params,
                                                batch, rng)

    Pass ``engine=`` to wrap an already-constructed :class:`KFACEngine`
    (the legacy ``repro.core.kfac.KFAC`` class is the same object); pass
    ``obs=`` (an ``repro.obs.Obs`` or ``ObsConfig``) to share one
    telemetry registry/log with the trainer — defaults to the engine's
    ``cfg.obs``."""
    eng = engine if engine is not None else KFACEngine(model, cfg or
                                                       KFACConfig(),
                                                       mesh, family)
    pipe = KFACPipeline(eng, obs=obs)
    return Optimizer(init=pipe.init, update=pipe.update, reject=eng.reject,
                     state_shardings=eng.state_shardings,
                     poll=pipe.poll if eng.refresh_mode == "overlap" else None,
                     engine=eng, name=f"kfac_{eng.cfg.inv_mode}")
