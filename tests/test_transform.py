"""The functional optimizer API (core/transform.py + optimizers/).

Pins three contracts:
  * the ``kfac(cfg)`` pipeline is BITWISE-identical to hand-driving the
    legacy ``KFAC`` stage methods with the paper's schedule, per inv_mode
    (the deprecation-shim parity — marked ``shim``);
  * the generic transforms (``sgd_momentum`` / ``adam``) match hand-rolled
    reference updates;
  * the typed states behave as ordinary pytrees (jit / eval_shape /
    legacy dict-style reads).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optimizers
from repro.configs.base import KFACConfig, TrainConfig
from repro.core import transform as TX
from repro.core.kfac import KFAC
from repro.data.pipeline import SyntheticAutoencoderData
from repro.models.mlp import MLP
from repro.training.trainer import Trainer
from repro.utils import tree as T


def _problem(dims=(32, 16, 8, 16, 32), n=256):
    mlp = MLP(list(dims), nonlin="tanh", loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    data = SyntheticAutoencoderData(dims[0], 6, n, seed=7)
    return mlp, params, data


def _assert_trees_equal(a, b, err=""):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(x, y, err_msg=err), a, b)


# ---------------------------------------------------------------------------
# legacy-shim parity: pipeline == manual five-call choreography, bitwise
# ---------------------------------------------------------------------------

def _legacy_loop(mlp, params, data, cfg, steps):
    """The pre-redesign Trainer.fit choreography, verbatim: stats →
    (multi+update3 | warmup/T3 refresh → eigen rescale → update) → lambda,
    each stage its own jit."""
    opt = KFAC(mlp, cfg, family="bernoulli")
    state = opt.init(params, data.batch(0))
    stats = jax.jit(opt.stats_grads)
    refresh = jax.jit(lambda s: opt.refresh_inverses(s, hot=True))
    rescale = jax.jit(opt.rescale_step)
    update = jax.jit(lambda s, p, g, b, r: opt.apply_update(s, p, g, b, r))
    multi = jax.jit(opt.refresh_multi)
    update3 = jax.jit(
        lambda s, p, g, b, r, gs, i3: opt.apply_update(
            s, p, g, b, r,
            cand_inv=[jax.tree.map(lambda x: x[c], i3) for c in range(3)],
            gammas=gs))
    lam_fn = jax.jit(opt.lambda_step)
    for step in range(steps):
        batch = data.batch(step)
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        state, grads, _ = stats(state, params, batch, rng)
        if cfg.t2 > 0 and step > 0 and step % cfg.t2 == 0:
            gs, i3 = multi(state)
            params, state, _ = update3(state, params, grads, batch, rng,
                                       gs, i3)
        else:
            if step < 3 or step % cfg.t3 == 0:
                state = refresh(state)
            if opt.eigen:
                state = rescale(state, grads)
            params, state, _ = update(state, params, grads, batch, rng)
        if cfg.t1 > 0 and (step + 1) % cfg.t1 == 0:
            state, _ = lam_fn(state, params, batch, rng)
    return params, state


def _pipeline_loop(mlp, params, data, cfg, steps):
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    state = opt.init(params, data.batch(0))
    for step in range(steps):
        batch = data.batch(step)
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        params, state, _ = opt.update(None, state, params, batch, rng)
    return params, state


@pytest.mark.shim
@pytest.mark.parametrize("inv_mode", ["blkdiag", "tridiag", "eigen"])
def test_pipeline_matches_legacy_bitwise(inv_mode):
    """10 autoencoder steps covering warmup, T3 refresh, a T2 gamma sweep
    and two T1 lambda steps: params must agree bit-for-bit."""
    mlp, params, data = _problem()
    cfg = KFACConfig(inv_mode=inv_mode, inverse_method="eigh",
                     lambda_init=1.0, t1=5, t2=4, t3=5, eta=1e-5)
    p_legacy, s_legacy = _legacy_loop(mlp, params, data, cfg, steps=10)
    p_pipe, s_pipe = _pipeline_loop(mlp, params, data, cfg, steps=10)
    _assert_trees_equal(p_legacy, p_pipe, err=f"params ({inv_mode})")
    np.testing.assert_array_equal(s_legacy.lam, s_pipe.lam)
    np.testing.assert_array_equal(s_legacy.gamma, s_pipe.gamma)
    np.testing.assert_array_equal(s_legacy.step, s_pipe.step)
    assert not np.array_equal(jax.tree.leaves(params)[0],
                              jax.tree.leaves(p_pipe)[0])  # it DID train


@pytest.mark.shim
def test_trainer_wraps_legacy_engine():
    """Trainer(model, KFAC(...)) — the deprecation shim — takes the exact
    same trajectory as Trainer(model, optimizers.kfac(...))."""
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    cfg = KFACConfig(lambda_init=1.0, t3=2, t1=2, t2=6)
    tc = TrainConfig(steps=6, seed=0, log_every=10_000)
    out1 = Trainer(mlp, KFAC(mlp, cfg, family="bernoulli"), tc).fit(
        params, data, steps=6, log=lambda *_: None)
    out2 = Trainer(mlp, optimizers.kfac(mlp, cfg, family="bernoulli"),
                   tc).fit(params, data, steps=6, log=lambda *_: None)
    _assert_trees_equal(out1["params"], out2["params"])
    assert [h["loss"] for h in out1["history"]] == \
        [h["loss"] for h in out2["history"]]


def test_kfac_requires_none_grads():
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=1.0),
                          family="bernoulli")
    batch = data.batch(0)
    state = opt.init(params, batch)
    with pytest.raises(ValueError, match="own gradients"):
        opt.update(T.tree_zeros_like(params), state, params, batch,
                   jax.random.PRNGKey(0))


def test_kfac_reject_raises_damping_and_clears_momentum():
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=2.0),
                          family="bernoulli")
    state = opt.init(params, data.batch(0))
    state = state.replace(delta0=jax.tree.map(
        lambda x: x + 1.0, state.delta0))
    rej = opt.reject(state)
    assert float(rej.lam) == pytest.approx(8.0)
    assert all(float(jnp.abs(leaf).max()) == 0.0
               for leaf in jax.tree.leaves(rej.delta0))


# ---------------------------------------------------------------------------
# typed state
# ---------------------------------------------------------------------------

def test_kfac_state_is_typed_pytree():
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=1.0),
                          family="bernoulli")
    batch = data.batch(0)
    state = opt.init(params, batch)
    assert isinstance(state, TX.KFACState)
    # dict-style legacy reads still work
    np.testing.assert_array_equal(state["lam"], state.lam)
    # flattens / jits / eval_shapes like any pytree
    n_leaves = len(jax.tree.leaves(state))
    assert n_leaves > 4
    rt = jax.jit(lambda s: s)(state)
    assert isinstance(rt, TX.KFACState) and len(jax.tree.leaves(rt)) == n_leaves
    abs_state = jax.eval_shape(opt.init, params, batch)
    assert isinstance(abs_state, TX.KFACState)
    assert abs_state.lam.dtype == jnp.float32
    # replace is functional
    s2 = state.replace(lam=jnp.float32(9.0))
    assert float(s2.lam) == 9.0 and float(state.lam) == 1.0


# ---------------------------------------------------------------------------
# generic transforms vs hand-rolled references
# ---------------------------------------------------------------------------

def _fake_grads(key, params):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.normal(k, x.shape, x.dtype)
                  for k, x in zip(keys, leaves)])


def test_sgd_momentum_transform_matches_reference_bitwise():
    """v <- m v - lr g; the chained scale(-lr) |> with_momentum recursion
    must reproduce it exactly (same op sequence, eager both sides)."""
    _, params, _ = _problem(dims=(16, 8, 16), n=64)
    lr, mom = 0.1, 0.9
    tx = optimizers.sgd_momentum_transform(lr=lr, momentum=mom)
    s = tx.init(params)
    vel = T.tree_zeros_like(params)
    for i in range(4):
        g = _fake_grads(jax.random.PRNGKey(i), params)
        u, s = tx.update(g, s, params)
        vel = jax.tree.map(lambda v, gg: mom * v + (-lr) * gg, vel, g)
        _assert_trees_equal(u, vel, err=f"step {i}")


def test_adam_transform_matches_reference():
    _, params, _ = _problem(dims=(16, 8, 16), n=64)
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8
    tx = optimizers.adam_transform(lr=lr, b1=b1, b2=b2, eps=eps)
    s = tx.init(params)
    mu = T.tree_zeros_like(params)
    nu = T.tree_zeros_like(params)
    for i in range(4):
        g = _fake_grads(jax.random.PRNGKey(i), params)
        u, s = tx.update(g, s, params)
        t = i + 1
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree.map(lambda v, gg: b2 * v + (1 - b2) * gg * gg, nu, g)
        ref = jax.tree.map(
            lambda m, v: -lr * ((m / (1 - b1 ** t))
                                / (jnp.sqrt(v / (1 - b2 ** t)) + eps)),
            mu, nu)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6,
                                                    atol=1e-7), u, ref)


def test_adam_weight_decay_is_decoupled():
    """AdamW ordering: the wd*p term must NOT be rescaled by 1/sqrt(nu)."""
    p = {"w": jnp.array([2.0, -4.0])}
    g = {"w": jnp.array([1.0, 1.0])}
    lr, wd = 0.1, 0.01
    tx = optimizers.adam_transform(lr=lr, weight_decay=wd)
    u, _ = tx.update(g, tx.init(p), p)
    tx0 = optimizers.adam_transform(lr=lr)
    u0, _ = tx0.update(g, tx0.init(p), p)
    np.testing.assert_allclose(u["w"], u0["w"] - lr * wd * p["w"],
                               rtol=1e-6, atol=1e-8)


def test_kfac_lambda_step_survives_nan_update():
    """A poisoned step at a T1 boundary: the update's guard keeps the old,
    finite params, the lambda stage evaluates the loss there (never at the
    NaN update), and lambda stays finite (a NaN rho leaves it as-is, the
    guard's reject then raises it, after the rule)."""
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    opt = optimizers.kfac(mlp, KFACConfig(lambda_init=1.0, t1=1, t3=1),
                          family="bernoulli")
    batch = data.batch(0)
    state = opt.init(params, batch)
    # one clean step so loss_prev/m_delta are real
    params, state, metrics = opt.update(None, state, params, batch,
                                        jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["rho"]))
    lam_before = float(state.lam)
    # poison the momentum tangent -> the next update is non-finite
    state = state.replace(delta0=jax.tree.map(
        lambda x: jnp.full_like(x, jnp.nan), state.delta0))
    new_params, state, metrics = opt.update(None, state, params, batch,
                                            jax.random.PRNGKey(1))
    assert not bool(metrics["finite"])
    _assert_trees_equal(new_params, params)
    # m_delta is NaN on a poisoned step, so rho is too — but lambda must
    # not be corrupted, and the reject still escalates it cleanly
    assert np.isnan(float(metrics["rho"]))
    assert np.isfinite(float(state.lam))
    assert float(state.lam) == pytest.approx(4 * lam_before)
    assert float(opt.reject(state).lam) == pytest.approx(16 * lam_before)
    assert all(float(jnp.abs(leaf).max()) == 0.0
               for leaf in jax.tree.leaves(state.delta0))


def test_sgd_momentum_optimizer_matches_hand_rolled_loop():
    """End-to-end through the Optimizer's own gradient pass."""
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    batch = data.batch(0)
    lr, mom = 0.1, 0.9
    opt = optimizers.sgd_momentum(mlp, lr=lr, momentum=mom)
    state = opt.init(params)
    p_opt = params

    def loss_fn(p, rng):
        (lt, _), _ = mlp.loss(p, None, batch, rng, mode="plain")
        return lt

    gfn = jax.jit(jax.grad(loss_fn))
    vel = T.tree_zeros_like(params)
    p_ref = params
    for i in range(5):
        rng = jax.random.PRNGKey(i)
        p_opt, state, metrics = opt.update(None, state, p_opt, batch, rng)
        g = gfn(p_ref, rng)
        vel = jax.tree.map(lambda v, gg: mom * v - lr * gg, vel, g)
        p_ref = jax.tree.map(lambda p, v: p + v, p_ref, vel)
        assert {"loss", "grad_norm", "delta_norm"} <= set(metrics)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        p_opt, p_ref)
    assert int(state.step) == 5


def test_clip_and_weight_decay_transforms():
    u = {"w": jnp.array([3.0, 4.0]), "b": jnp.zeros(2)}
    p = {"w": jnp.array([10.0, 0.0]), "b": jnp.ones(2)}
    clip = TX.clip_by_global_norm(1.0)
    out, _ = clip.update(u, clip.init(p), p)
    np.testing.assert_allclose(float(jnp.sqrt(T.tree_sqnorm(out))), 1.0,
                               rtol=1e-6)
    # under the bound: passthrough
    out2, _ = TX.clip_by_global_norm(100.0).update(u, (), p)
    _assert_trees_equal(out2, u)
    wd = TX.add_decayed_weights(0.1)
    out3, _ = wd.update(u, wd.init(p), p)
    np.testing.assert_allclose(out3["w"], u["w"] + 0.1 * p["w"])
    np.testing.assert_allclose(out3["b"], u["b"] + 0.1 * p["b"])


def test_chain_threads_state_and_updates():
    p = {"w": jnp.arange(4.0)}
    tx = TX.chain(TX.scale(2.0), TX.scale(0.5), TX.with_momentum(0.0))
    s = tx.init(p)
    assert isinstance(s, tuple) and len(s) == 3
    u, s = tx.update({"w": jnp.ones(4)}, s, p)
    np.testing.assert_allclose(u["w"], jnp.ones(4))


def test_from_transform_requires_model_or_grads():
    opt = optimizers.sgd_momentum(None, lr=0.1)
    p = {"w": jnp.ones(3)}
    state = opt.init(p)
    with pytest.raises(ValueError, match="no model"):
        opt.update(None, state, p, None, None)
    # explicit-grads (pure optax-style) path works without a model
    newp, state, metrics = opt.update({"w": jnp.ones(3)}, state, p)
    np.testing.assert_allclose(newp["w"], 1.0 - 0.1)
    assert float(metrics["delta_norm"]) > 0


def test_with_kl_clip_matches_hand_rolled():
    """ν = min(1, sqrt(max_kl / (lr²·|Δᵀg|))) against an explicit
    reference, wrapping a plain lr scale (Δ = -lr·g ⇒ |Δᵀg| = lr·|g|²)."""
    g = {"w": jnp.array([3.0, -4.0]), "b": jnp.array([1.0, 2.0, -2.0])}
    p = T.tree_scale(g, 0.0)
    lr, max_kl = 0.1, 1e-3
    tx = TX.with_kl_clip(TX.scale(-lr), max_kl, lr=1.0)
    out, _ = tx.update(g, tx.init(p), p)

    delta = T.tree_scale(g, -lr)
    quad = abs(float(T.tree_dot(delta, g)))
    nu = min(1.0, float(np.sqrt(max_kl / quad)))
    assert nu < 1.0                       # the clip actually engaged
    jax.tree_util.tree_map(
        lambda o, d: np.testing.assert_allclose(o, nu * d, rtol=1e-6),
        out, delta)

    # generous budget: passthrough, bitwise
    tx2 = TX.with_kl_clip(TX.scale(-lr), 1e6, lr=1.0)
    out2, _ = tx2.update(g, tx2.init(p), p)
    _assert_trees_equal(out2, delta)

    # the explicit-lr form: inner emits the raw direction Δ = -g and the
    # caller applies lr·Δ, so the trust region is on lr²·|Δᵀg|
    tx3 = TX.with_kl_clip(TX.scale(-1.0), max_kl, lr=lr)
    out3, _ = tx3.update(g, tx3.init(p), p)
    nu3 = min(1.0, float(np.sqrt(
        max_kl / (lr * lr * abs(float(T.tree_dot(g, g)))))))
    jax.tree_util.tree_map(
        lambda o, gg: np.testing.assert_allclose(o, -nu3 * gg, rtol=1e-6),
        out3, g)


def test_kfac_kl_clip_engine_paths():
    """KFACConfig.kl_clip on the fused fixed-lr update: a generous budget
    is bitwise-identical to kl_clip=0 (off), a tight one shrinks every
    step and tracks the hand-computed ν."""
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    batch = data.batch(0)

    def run(kl_clip, steps=4):
        cfg = KFACConfig(inv_mode="blkdiag", use_rescale=False,
                         fixed_lr=0.05, lambda_init=1.0, t3=2,
                         kl_clip=kl_clip)
        opt = optimizers.kfac(mlp, cfg, family="bernoulli")
        state = opt.init(params, batch)
        p, norms = params, []
        for step in range(steps):
            p, state, metrics = opt.update(
                None, state, p, batch,
                jax.random.fold_in(jax.random.PRNGKey(3), step))
            norms.append(float(metrics["delta_norm"]))
        return p, norms

    p_off, n_off = run(0.0)
    p_huge, n_huge = run(1e9)
    _assert_trees_equal(p_off, p_huge, "huge kl_clip must be a no-op")
    np.testing.assert_array_equal(n_off, n_huge)

    p_tight, n_tight = run(1e-5)
    assert all(t < o for t, o in zip(n_tight, n_off))
    assert not np.allclose(jax.tree.leaves(p_tight)[0],
                           jax.tree.leaves(p_off)[0])


# ---------------------------------------------------------------------------
# baselines race through the SAME Trainer.fit loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_opt", [
    lambda m: optimizers.sgd_momentum(m, lr=0.1, momentum=0.9),
    lambda m: optimizers.adam(m, lr=1e-2),
    lambda m: optimizers.get("kfac", m, kfac_cfg=KFACConfig(
        lambda_init=1.0, t3=2), family="bernoulli"),
], ids=["sgd_momentum", "adam", "kfac"])
def test_optimizers_race_through_one_trainer(make_opt):
    mlp, params, data = _problem(dims=(16, 8, 16), n=64)
    tr = Trainer(mlp, make_opt(mlp),
                 TrainConfig(steps=8, seed=0, log_every=10_000))
    out = tr.fit(params, data, steps=8, log=lambda *_: None)
    losses = [h["loss"] for h in out["history"]]
    assert len(losses) == 8
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
