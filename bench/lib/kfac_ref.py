"""Plain K-FAC reference: the paper's Algorithm 2 with its schedule.

Martens & Grosse (2015), with the choices the benchmarked configuration
states (``traffic["optimizer"]``): block-diagonal Kronecker factors with
the decayed running estimate of section 5 (ε = min(1 − 1/k, cap)), the
factored Tikhonov damping of section 6.3 with the trace-norm π, inverses
by Newton–Schulz iteration (hot-started from the previous inverse when
that start is safe, else from I/‖M‖∞), the exact-Fisher re-scaling and
momentum of sections 6.4 and 7 (a 2×2 solve on the quadratic model), l2
coefficient η, and the schedule of sections 6.5, 6.6 and 8:

* the inverses are refreshed on the first three steps of a run and then
  on every step ``k`` with ``k % T3 == 0``; in between they go stale;
* on every step ``k > 0`` with ``k % T2 == 0`` the γ sweep replaces the
  refresh: inverses for γ, ω₂γ and γ/ω₂ (ω₂ = √(19/20)^T2), each from
  I/‖M‖∞, and the candidate whose quadratic model (with momentum) is
  lowest sets the update, γ and the inverses kept;
* after the update of every step with ``(k + 1) % T1 == 0`` the
  Levenberg–Marquardt rule moves λ by ω₁ = (19/20)^T1 on the reduction
  ratio ρ = (h(θ + δ) − h(θ)) / M(δ), measured on that step's batch.

Untagged parameters get a diagonal curvature: the running mean of the
squared gradient.

The model is duck-typed (see ``bench/reference/*.py``): it gives
``blocks`` (name -> dict(path, a_kind, g_kind, a_dim, g_dim)),
``loss_grad_stats(params, batch, key)``, ``loss(params, batch)`` and
``fisher_quad(params, batch, tangents)``.  The step ``k`` uses the key
``fold_in(PRNGKey(seed), k)`` for its sampled targets' stream, folded
once more with 1, as the configuration's trainer does.  This module
imports nothing of the program under test.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

_TINY = 1e-20


def get_path(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def set_path(tree, path, value):
    if not path:
        return value
    k, rest = path[0], path[1:]
    if isinstance(tree, tuple):
        lst = list(tree)
        lst[k] = set_path(lst[k], rest, value)
        return tuple(lst)
    out = dict(tree)
    out[k] = set_path(out[k], rest, value)
    return out


def _trace(x, kind):
    return jnp.sum(x, -1) if kind == "diag" else jnp.trace(x, axis1=-2,
                                                            axis2=-1)


def ns_inverse(m, iters, x0=None):
    """Newton–Schulz X ← X(2I − MX) on SPD ``m`` (batched)."""
    d = m.shape[-1]
    eye = jnp.eye(d, dtype=m.dtype)
    norm = jnp.max(jnp.sum(jnp.abs(m), -1), -1)
    cold = eye / norm[..., None, None]
    if x0 is None:
        x = cold
    else:
        r = eye - m @ x0
        bad = jnp.max(jnp.sum(jnp.abs(r), -1), -1) >= 1.0
        x = jnp.where(bad[..., None, None], cold, x0)
    for _ in range(iters):
        x = x @ (2.0 * eye - m @ x)
    return 0.5 * (x + jnp.swapaxes(x, -1, -2))


def damped_inverse(blk, fac, gamma, iters, prev):
    pi = jnp.sqrt(jnp.maximum(_trace(fac["a"], blk["a_kind"]) / blk["a_dim"],
                              _TINY)
                  / jnp.maximum(_trace(fac["g"], blk["g_kind"])
                                / blk["g_dim"], _TINY))
    out = {}
    for side, damp in (("a", pi * gamma), ("g", gamma / pi)):
        kind, x = blk[f"{side}_kind"], fac[side]
        if kind == "diag":
            out[side] = 1.0 / jnp.maximum(x + damp[..., None], _TINY)
        else:
            m = x + damp[..., None, None] * jnp.eye(x.shape[-1])
            out[side] = ns_inverse(m, iters,
                                   None if prev is None else prev[side])
    return out


def precondition(blk, inv, v):
    """Ā⁻¹ V G⁻¹ for V stored (…, d_in, d_out)."""
    if blk["a_kind"] == "diag":
        u = v * inv["a"][..., :, None]
    else:
        u = jnp.einsum("...ij,...jk->...ik", inv["a"], v)
    if blk["g_kind"] == "diag":
        return u * inv["g"][..., None, :]
    return jnp.einsum("...ij,...jk->...ik", u, inv["g"])


_INVERSES = {}


def _inverse(name, blk, iters):
    """The jitted damped inverse of one block (cached per block)."""
    key = (name, tuple(sorted(blk.items())), iters)
    if key not in _INVERSES:
        _INVERSES[key] = jax.jit(
            lambda fac, gamma, prev: damped_inverse(blk, fac, gamma, iters,
                                                    prev))
    return _INVERSES[key]


def _dot(a, b):
    return sum(jnp.vdot(x, y) for x, y in zip(jax.tree.leaves(a),
                                             jax.tree.leaves(b)))


LAM_MIN, LAM_MAX = 1e-8, 1e8
GAMMA_MIN, GAMMA_MAX = 1e-6, 1e4


def _quad_choice(q, bvec, c, m):
    """(α, μ, M) of candidate ``c`` with the momentum tangent ``m − 1``."""
    idx = jnp.array([c, m - 1])
    q2 = q[jnp.ix_(idx, idx)] + 1e-20 * jnp.eye(2)
    b2 = bvec[idx]
    x = -jnp.linalg.solve(q2, b2)
    return x[0], x[1], 0.5 * x @ q2 @ x + b2 @ x


def run(model, params, batches, seed, opt, steps=3,
        param_dtype=jnp.float32, ns_precision=None):
    """Drive ``steps`` K-FAC steps from ``params`` over ``batches[k]``,
    keeping the parameters in ``param_dtype`` (the optimizer's own state
    stays float32); ``ns_precision``, where given, is the matmul
    precision of the inverses alone.

    Returns dict(losses=[…], first_update=<pytree>, change=<pytree>,
    last_update=<pytree>, first_grad=<pytree>, lam=[…], gamma=[…]) — the
    update applied by step 0, the parameters after the last step less
    those before the first, the update applied by the last step, the
    gradient of step 0 (all float32), and λ and γ after each step."""
    eta, cap, iters = opt["eta"], opt["decay_cap"], opt["ns_iters"]
    t1, t2, t3 = opt["t1"], opt["t2"], opt["t3"]
    omega1 = (19.0 / 20.0) ** t1
    omega2 = math.sqrt(19.0 / 20.0) ** t2
    lam = float(opt["lambda_init"])
    gamma = math.sqrt(lam + eta)
    blocks = model.blocks
    tagged = {tuple(b["path"]) for b in blocks.values()}
    params = jax.tree.map(lambda x: jnp.asarray(x, param_dtype), params)
    params0 = params
    factors, inv = {}, {}
    for name, b in blocks.items():
        w = get_path(params, b["path"])
        lead = w.shape[:-2]
        factors[name] = {
            s: jnp.zeros(lead + ((b[f"{s}_dim"],) if b[f"{s}_kind"] == "diag"
                                 else (b[f"{s}_dim"],) * 2), jnp.float32)
            for s in ("a", "g")}
        inv[name] = {s: (jnp.ones_like(x) if b[f"{s}_kind"] == "diag"
                         else x + jnp.eye(x.shape[-1]))
                     for s, x in factors[name].items()}
    untagged = [
        tuple(getattr(k, "key", getattr(k, "idx", None)) for k in kp)
        for kp, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    untagged = [p for p in untagged if p not in tagged]
    diag = {p: jnp.zeros_like(get_path(params, p)) for p in untagged}
    delta0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, lams, gammas_kept = [], [], []
    first_update, first_grad = None, None
    base = jax.random.PRNGKey(seed)

    def inverses(gam, prev):
        with (jax.default_matmul_precision(ns_precision) if ns_precision
              else contextlib.nullcontext()):
            return {n: _inverse(n, blocks[n], iters)(
                factors[n], gam, None if prev is None else prev[n])
                for n in blocks}

    for k in range(steps):
        batch = batches[k]
        rng = jax.random.fold_in(base, k)
        loss, grads, contrib = model.loss_grad_stats(
            params, batch, jax.random.fold_in(rng, 1))
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = grads
        eps = min(1.0 - 1.0 / (k + 1), cap)
        factors = {n: {s: eps * factors[n][s] + (1 - eps) * contrib[n][s]
                       for s in ("a", "g")} for n in factors}
        diag = {p: eps * d + (1 - eps) * jnp.square(get_path(grads, p))
                for p, d in diag.items()}
        if t2 > 0 and k > 0 and k % t2 == 0:
            cands = [gamma, min(max(gamma * omega2, GAMMA_MIN), GAMMA_MAX),
                     min(max(gamma / omega2, GAMMA_MIN), GAMMA_MAX)]
            invs = [inverses(g, None) for g in cands]
        else:
            if k < 3 or (t3 > 0 and k % t3 == 0):
                inv = inverses(gamma, inv)
            cands, invs = [gamma], [inv]
        greg = jax.tree.map(lambda g, p: g + eta * p, grads, p32)
        deltas = []
        for iv in invs:
            delta = greg
            for p, d in diag.items():
                delta = set_path(delta, p, -get_path(greg, p) / (d + lam + eta))
            for n, b in blocks.items():
                delta = set_path(delta, b["path"], -precondition(
                    b, iv[n], get_path(greg, b["path"])))
            deltas.append(delta)
        tangents = deltas + [delta0]
        m = len(tangents)
        q = model.fisher_quad(p32, batch, tangents)
        dots = jnp.array([[_dot(a, b) for b in tangents] for a in tangents])
        q = q + (lam + eta) * dots
        bvec = jnp.array([_dot(greg, t) for t in tangents])
        choices = [_quad_choice(q, bvec, c, m) for c in range(len(invs))]
        c_star = int(jnp.argmin(jnp.stack([ch[2] for ch in choices])))
        alpha, mu, m_delta = choices[c_star]
        step = jax.tree.map(lambda d, mo: alpha * d + mu * mo,
                            deltas[c_star], delta0)
        params = jax.tree.map(lambda p, d: (p + d).astype(param_dtype),
                              p32, step)
        gamma, inv = float(cands[c_star]), invs[c_star]
        delta0 = step
        if first_update is None:
            first_update = step
        if t1 > 0 and (k + 1) % t1 == 0:
            rho = (float(model.loss(params, batch)) - float(loss)) / min(
                float(m_delta), -1e-20)
            if rho > 0.75:
                lam = lam * omega1
            if rho < 0.25:
                lam = lam / omega1
            lam = min(max(lam, LAM_MIN), LAM_MAX)
        lams.append(lam)
        gammas_kept.append(gamma)
    change = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, params0)
    return dict(losses=losses, first_update=first_update, change=change,
                last_update=delta0, first_grad=first_grad, lam=lams,
                gamma=gammas_kept)
