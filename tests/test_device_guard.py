"""The non-finite guard on the device, and metrics read a step late.

Pins two contracts of ``Trainer.fit`` over the K-FAC pipeline:
  * the guard traced into the update programs (``KFACEngine.guard``) and
    the λ program leaves bit for bit what the trainer's host guard left:
    params, λ, momentum, step and the rejected count, with the poisoned
    update on a plain step, a λ step and a γ-sweep step, on the rescale
    and the fixed-lr routes; an optimizer without the device flag (a
    first-order baseline) still has its update skipped on the host;
  * reading each step's metrics one step late, in one transfer, gives the
    history the synchronous loop gives, drains the last row when the loop
    ends (by preemption too), returns params and state already computed,
    and prints each ``log_every`` line once.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optimizers
from repro.configs.base import KFACConfig, TrainConfig
from repro.data.pipeline import SyntheticAutoencoderData
from repro.models.mlp import MLP
from repro.obs import Obs
from repro.optimizers.kfac import KFACEngine
from repro.training.trainer import Trainer
from repro.utils import tree as T

# λ rule after steps 2 and 5, γ sweep on step 4, refresh on 0–2 and 3, 6
T1, T2, T3, STEPS = 3, 4, 3, 8
POISON = {"plain": 3, "lambda": 5, "gamma": 4}
ROUTES = {"rescale": {}, "fixed-lr": {"use_rescale": False,
                                      "fixed_momentum": 0.5}}


def _problem():
    mlp = MLP([16, 8, 16], nonlin="tanh", loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    data = SyntheticAutoencoderData(16, 6, 64, seed=7)
    return mlp, params, data


def _cfg(route):
    return KFACConfig(lambda_init=1.0, t1=T1, t2=T2, t3=T3, eta=1e-5,
                      **ROUTES[route])


def _nan_momentum(state):
    return state.replace(delta0=jax.tree.map(
        lambda x: jnp.full_like(x, jnp.nan), state.delta0))


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _host_guard_loop(mlp, params, data, cfg, poison):
    """The trainer's host guard over the engine's raw stages, as the loop
    ran before the guard moved onto the device: the λ stage reads the new
    params' finiteness and evaluates ρ at the params that will be kept;
    the trainer then reads the new params and the update's norm and, on a
    non-finite update, keeps the old params and applies ``reject`` after
    the λ rule."""
    eng = KFACEngine(mlp, cfg, family="bernoulli")
    state = eng.init(params, data.batch(0))
    stats = jax.jit(eng.stats_grads)
    refresh = jax.jit(lambda s: eng.refresh_inverses(s, hot=True))
    multi = jax.jit(eng.refresh_multi)
    if cfg.use_rescale:
        update = jax.jit(lambda s, p, g, b, r: eng.apply_update(s, p, g, b,
                                                                r))
        update3 = jax.jit(lambda s, p, g, b, r, gs, i3: eng.apply_update(
            s, p, g, b, r, gammas=gs,
            cand_inv=[jax.tree.map(lambda x: x[c], i3) for c in range(3)]))
    else:
        update = jax.jit(lambda s, p, g, b, r: eng.apply_update_fused(
            s, p, g, b, r))
        update3 = jax.jit(lambda s, p, g, b, r, gs, i3:
                          eng.apply_update_fused(
                              s, p, g, b, r, gamma_override=gs[0],
                              inv_override=jax.tree.map(lambda x: x[0], i3)))
    lam_fn = jax.jit(eng.lambda_step)
    rejected = 0
    for step in range(STEPS):
        batch = data.batch(step)
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        if step == poison:
            state = _nan_momentum(state)
        state, grads, _ = stats(state, params, batch, rng)
        if step > 0 and step % T2 == 0:
            new, state, m = update3(state, params, grads, batch, rng,
                                    *multi(state))
        else:
            if step < 3 or step % T3 == 0:
                state = refresh(state)
            new, state, m = update(state, params, grads, batch, rng)
        if (step + 1) % T1 == 0:
            keep = new if bool(T.tree_isfinite(new)) else params
            state, _ = lam_fn(state, keep, batch, rng)
        if bool(T.tree_isfinite(new)) and np.isfinite(float(m["delta_norm"])):
            params = new
        else:
            state = eng.reject(state)
            rejected += 1
    return params, state, rejected


def _poisoned(update, at):
    """``update`` with NaN momentum handed to its ``at``-th call."""
    calls = []

    def step(grads, state, params, batch, rng):
        if len(calls) == at:
            state = _nan_momentum(state)
        calls.append(None)
        return update(grads, state, params, batch, rng)
    return step


def _fit(mlp, opt, params, data, steps, **tc):
    obs = Obs()
    tr = Trainer(mlp, opt, TrainConfig(steps=steps, seed=0,
                                       **{"log_every": 10 ** 9, **tc}),
                 obs=obs)
    return tr, tr.fit(params, data, steps, log=lambda *_: None), obs


@pytest.mark.parametrize("where", sorted(POISON))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_device_guard_matches_host_guard(route, where):
    mlp, params, data = _problem()
    cfg = _cfg(route)
    p_ref, s_ref, rejected = _host_guard_loop(mlp, params, data, cfg,
                                              POISON[where])
    assert rejected == 1
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    opt = dataclasses.replace(opt, update=_poisoned(opt.update,
                                                    POISON[where]))
    _, out, obs = _fit(mlp, opt, params, data, STEPS)
    _assert_trees_equal(out["params"], p_ref)
    _assert_trees_equal(out["state"].delta0, s_ref.delta0)
    np.testing.assert_array_equal(out["state"].lam, s_ref.lam)
    np.testing.assert_array_equal(out["state"].step, s_ref.step)
    reg = obs.registry
    assert reg.counter("train/rejected_steps").value == rejected
    assert reg.counter("train/device_guard_steps").value == STEPS
    assert reg.counter("train/host_syncs",
                       {"site": "train/finite_check"}).value == 0
    assert not np.isfinite(out["history"][POISON[where]]["delta_norm"])


def test_baseline_without_flag_takes_the_host_guard():
    """A first-order baseline's metrics carry no ``finite`` flag: the
    trainer reads the check on the host and skips a NaN update."""
    mlp, params, data = _problem()
    opt = optimizers.sgd_momentum(mlp, lr=0.1, momentum=0.9)
    _, clean, _ = _fit(mlp, opt, params, data, 2)
    update = opt.update

    def nan_on_third(grads, state, p, batch, rng):
        new, state, metrics = update(grads, state, p, batch, rng)
        if len(calls) == 2:
            new = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), new)
        calls.append(None)
        return new, state, metrics
    calls = []
    opt = dataclasses.replace(opt, update=nan_on_third)
    _, out, obs = _fit(mlp, opt, params, data, 3)
    _assert_trees_equal(out["params"], clean["params"])
    reg = obs.registry
    assert reg.counter("train/rejected_steps").value == 1
    assert reg.counter("train/device_guard_steps").value == 0
    assert reg.counter("train/host_syncs",
                       {"site": "train/finite_check"}).value == 2 + 2 + 1
    assert len(out["history"]) == 3


def _sync_history(mlp, params, data, cfg, steps):
    """The synchronous loop: each step's scalar metrics read right after
    its update."""
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    state = opt.init(params, data.batch(0))
    rows = []
    for step in range(steps):
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        params, state, metrics = opt.update(None, state, params,
                                            data.batch(step), rng)
        rows.append({k: float(v) for k, v in metrics.items()
                     if k != "finite" and jnp.ndim(v) == 0})
    return rows


class _Preempting:
    """Data that raises the trainer's preemption flag when the batch of
    step ``at`` is made."""

    def __init__(self, data, at):
        self.data, self.at, self.trainer = data, at, None

    def batch(self, step):
        if step == self.at:
            self.trainer._preempted = True
        return self.data.batch(step)


@pytest.mark.parametrize("preempt_at", [None, 4])
def test_late_metrics_match_the_synchronous_loop(preempt_at, monkeypatch):
    mlp, params, data = _problem()
    cfg = _cfg("rescale")
    want = _sync_history(mlp, params, data, cfg, STEPS)
    blocked = []
    block = jax.block_until_ready

    def recording(x):
        blocked.append(x)
        return block(x)
    monkeypatch.setattr(jax, "block_until_ready", recording)

    lines = []
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    tr = Trainer(mlp, opt, TrainConfig(steps=STEPS, seed=0, log_every=2),
                 obs=Obs())
    src = _Preempting(data, preempt_at)
    src.trainer = tr
    out = tr.fit(params, src, STEPS, log=lines.append)

    ran = STEPS if preempt_at is None else preempt_at + 1
    assert out["history"] == want[:ran]        # same keys, same floats
    assert [list(h) for h in out["history"]] == [list(h) for h in want[:ran]]
    # fit blocked on what it returns: nothing of it is still pending
    leaves = jax.tree.leaves((out["params"], out["state"]))
    assert blocked
    last = {id(x) for x in jax.tree.leaves(blocked[-1])}
    assert all(id(x) in last for x in leaves)
    assert all(x.is_ready() for x in leaves)
    # each log_every line once, in order; the preemption line after them
    steps_logged = [int(s.split()[2].rstrip(":")) for s in lines
                    if "loss=" in s]
    assert steps_logged == list(range(0, ran, 2))
    if preempt_at is not None:
        assert lines[-1] == f"[trainer] preempted at step {preempt_at}; " \
            "checkpointing"
