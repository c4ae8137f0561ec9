"""The kernel autotuner (repro.kernels.autotune) and the backward-pass
fusion of the factor statistics / fixed-lr update chain.

Covers the PR's contracts:
  * cache hit/miss determinism (injectable timer, call counting),
  * a corrupted or stale on-disk cache re-tunes — never crashes,
  * ``autotune="off"`` is bitwise the untuned path,
  * fused backward factor accumulation allclose-matches the two-pass
    statistics per inv_mode (tridiag disables fusion),
  * the fused precondition+momentum+clip stage matches the three-op
    reference, and ``momentum_global_clip`` matches its chained form,
  * the ``update_chain`` kernel matches the einsum reference.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import KFACConfig
from repro.core import transform as TF
from repro.data.pipeline import SyntheticAutoencoderData
from repro.kernels import autotune as at
from repro.models.mlp import MLP
from repro.optimizers.kfac import KFACEngine
from repro.utils import tree as T

SHAPE = (256, 128)                      # factor_update problem: x (N, d)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own cache file and a clean in-process memo, and
    never sees a REPRO_AUTOTUNE override from the environment."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    at.clear_memo()
    yield
    at.clear_memo()


def _counting_timer():
    calls = {"n": 0}

    def timer(fn, iters=3):
        calls["n"] += 1
        jax.block_until_ready(fn())
        return float(calls["n"])        # first legal candidate wins
    return timer, calls


# ---------------------------------------------------------------------------
# cache behaviour
# ---------------------------------------------------------------------------

def test_cache_miss_tunes_then_hits():
    timer, calls = _counting_timer()
    cfg = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                   mode="cache", timer=timer)
    assert cfg in at.candidates("factor_update", SHAPE)
    n_tuned = calls["n"]
    assert n_tuned == len(at.candidates("factor_update", SHAPE))
    # in-process memo hit: no re-timing
    assert at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="cache", timer=timer) == cfg
    assert calls["n"] == n_tuned
    # fresh-process simulation: disk hit, still no re-timing
    at.clear_memo()
    assert at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="cache", timer=timer) == cfg
    assert calls["n"] == n_tuned


def test_cache_is_deterministic_given_timings():
    timer1, _ = _counting_timer()
    cfg1 = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="cache", timer=timer1)
    os.remove(at.cache_path())
    at.clear_memo()
    timer2, _ = _counting_timer()
    cfg2 = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="cache", timer=timer2)
    assert cfg1 == cfg2


def test_corrupted_cache_retunes_never_crashes():
    timer, calls = _counting_timer()
    at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
             mode="cache", timer=timer)
    n = calls["n"]
    with open(at.cache_path(), "w") as f:
        f.write("{this is not json")
    at.clear_memo()
    cfg = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                   mode="cache", timer=timer)
    assert cfg in at.candidates("factor_update", SHAPE)
    assert calls["n"] > n                 # it re-tuned
    # and the rewritten cache is valid again
    assert at.load_cache() != {}


def test_stale_cache_entry_retunes():
    timer, calls = _counting_timer()
    key = at.cache_key("factor_update", SHAPE, jnp.float32,
                       at.backend_tag(True))
    # a winner that is no longer a legal candidate (constraints changed)
    at.save_entry(key, {"cfg": {"bm": 999, "bn": 3, "bk": 7}, "us": 1.0,
                        "timings": {}})
    cfg = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                   mode="cache", timer=timer)
    assert cfg in at.candidates("factor_update", SHAPE)
    assert calls["n"] > 0


def test_env_override_wins(monkeypatch):
    timer, calls = _counting_timer()
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="cache", timer=timer) is None
    assert calls["n"] == 0


def test_off_returns_none_and_no_candidates_is_none():
    assert at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                    mode="off") is None
    # ragged problem: no legal candidate -> None (caller falls back)
    timer, _ = _counting_timer()
    assert at.tuned("precond", (100, 37), jnp.float32, interpret=True,
                    mode="cache", timer=timer) is None


def test_autotune_off_is_bitwise_untuned():
    """autotune="off" feeds the kernel its built-in default blocks — the
    exact same program as before the autotuner existed."""
    from repro.kernels.factor_update import factor_update
    x = jax.random.normal(jax.random.PRNGKey(0), SHAPE, jnp.float32)
    c = jax.random.normal(jax.random.PRNGKey(1), (SHAPE[1], SHAPE[1]))
    cfg = at.tuned("factor_update", SHAPE, jnp.float32, interpret=True,
                   mode="off") or {}
    assert cfg == {}
    out_off = factor_update(x, c, alpha=0.1, beta=0.9, interpret=True, **cfg)
    out_ref = factor_update(x, c, alpha=0.1, beta=0.9, interpret=True)
    assert np.array_equal(np.asarray(out_off), np.asarray(out_ref))


def test_tuned_config_changes_tiles_not_results():
    from repro.kernels.factor_update import factor_update
    x = jax.random.normal(jax.random.PRNGKey(0), SHAPE, jnp.float32)
    c = jax.random.normal(jax.random.PRNGKey(1), (SHAPE[1], SHAPE[1]))
    ref = factor_update(x, c, alpha=0.1, beta=0.9, interpret=True)
    for cfg in at.candidates("factor_update", SHAPE):
        out = factor_update(x, c, alpha=0.1, beta=0.9, interpret=True, **cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# update_chain kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 128), (256, 128)])
def test_update_chain_kernel_matches_reference(shape):
    from repro.kernels.update_chain import precond_momentum
    d_in, d_out = shape
    k = jax.random.PRNGKey(0)
    a = jax.random.normal(k, (d_in, d_in))
    v = jax.random.normal(jax.random.fold_in(k, 1), (d_in, d_out))
    g = jax.random.normal(jax.random.fold_in(k, 2), (d_out, d_out))
    m = jax.random.normal(jax.random.fold_in(k, 3), (d_in, d_out))
    alpha, mu = jnp.float32(-0.05), jnp.float32(0.9)
    d, sq = precond_momentum(a, v, g, m, alpha=alpha, mu=mu, interpret=True)
    ref = alpha * (a @ v @ g) + mu * m
    np.testing.assert_allclose(np.asarray(d), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(sq), float(jnp.sum(ref * ref)),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# fused backward-pass statistics vs the two-pass reference
# ---------------------------------------------------------------------------

def _mlp_engine(fused, backend="xla", inv_mode="blkdiag"):
    dims = [16, 16, 8, 16, 16]
    mlp = MLP(dims, nonlin="tanh", loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    data = SyntheticAutoencoderData(dims[0], 8, 256, seed=7)
    batch = data.batch(0)
    cfg = KFACConfig(kernel_backend=backend, inv_mode=inv_mode,
                     fused_stats=fused)
    return KFACEngine(mlp, cfg, family="bernoulli"), params, batch


def _run_stats(eng, params, batch, steps=3):
    state = eng.init(params, batch)
    for step in range(steps):
        rng = jax.random.PRNGKey(100 + step)
        state, _, _ = jax.jit(eng.stats_grads)(state, params, batch, rng)
    return state


@pytest.mark.parametrize("inv_mode", ["blkdiag", "eigen"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fused_stats_match_two_pass(inv_mode, backend):
    eng0, params, batch = _mlp_engine(False, backend="xla",
                                      inv_mode=inv_mode)
    eng1, _, _ = _mlp_engine(True, backend=backend, inv_mode=inv_mode)
    assert eng1.fused_names, "dense MLP layers must be fused-eligible"
    s0 = _run_stats(eng0, params, batch)
    s1 = _run_stats(eng1, params, batch)
    for name in s0.factors:
        for side in ("a", "g"):
            a = np.asarray(s0.factors[name][side])
            b = np.asarray(s1.factors[name][side])
            np.testing.assert_allclose(
                b, a, rtol=1e-5, atol=1e-6,
                err_msg=f"{inv_mode}/{backend} {name}.{side}")


def test_tridiag_disables_fusion():
    eng, _, _ = _mlp_engine(True, inv_mode="tridiag")
    assert not eng.fused and not eng.fused_names


def test_fused_probe_shape_is_tiny():
    eng, params, batch = _mlp_engine(True)
    probes = eng._probes(batch)
    for name in eng.fused_names:
        p = probes[name]
        assert isinstance(p, dict) and set(p) == {"gg"}
        g = eng.metas[name].g_dim
        assert p["gg"].shape == (g, g)


# ---------------------------------------------------------------------------
# the fused fixed-lr update chain vs the three-op reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inv_mode", ["blkdiag", "eigen", "tridiag"])
def test_fused_update_matches_three_op_reference(inv_mode):
    dims = [16, 16, 8, 16, 16]
    mlp = MLP(dims, nonlin="tanh", loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    data = SyntheticAutoencoderData(dims[0], 8, 256, seed=7)
    batch = data.batch(0)
    cfg = KFACConfig(inv_mode=inv_mode, use_rescale=False, fixed_lr=0.05,
                     fixed_momentum=0.9, clip_delta_norm=1e-3)
    eng = KFACEngine(mlp, cfg, family="bernoulli")
    state = eng.init(params, batch)
    rng = jax.random.PRNGKey(7)
    state, grads, _ = eng.stats_grads(state, params, batch, rng)
    state = eng.refresh_inverses(state)
    # nonzero velocity so the momentum term and the clip both bite
    state = state.replace(delta0=jax.tree.map(
        lambda d: 0.01 * jax.random.normal(jax.random.PRNGKey(9),
                                           d.shape, d.dtype), state.delta0))

    p_fused, s_fused, m = eng.apply_update_fused(state, params, grads,
                                                 batch, rng)

    # reference: precondition, momentum, global clip, apply — as three
    # separate ops over materialized intermediates
    grads_reg = T.tree_axpy(cfg.eta, T.tree_cast(params, jnp.float32),
                            T.tree_cast(grads, jnp.float32))
    delta = T.tree_scale(eng._precondition(grads_reg, state.inv, state),
                         cfg.fixed_lr)
    vel = jax.tree.map(lambda d, mo: d + cfg.fixed_momentum * mo,
                       delta, state.delta0)
    norm = jnp.sqrt(T.tree_sqnorm(vel))
    factor = jnp.minimum(1.0, cfg.clip_delta_norm / jnp.maximum(norm, 1e-20))
    p_ref = jax.tree.map(lambda p, d: p + (factor * d).astype(p.dtype),
                         params, vel)

    for a, b in zip(jax.tree.leaves(p_fused), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # stored velocity is pre-clip (with_momentum semantics)
    for a, b in zip(jax.tree.leaves(s_fused.delta0), jax.tree.leaves(vel)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(m["delta_norm"]), float(factor * norm),
                               rtol=1e-5)


def _pipe(opt):
    # Optimizer.update is the bound KFACPipeline.update
    return opt.update.__self__


def test_fused_stage_name_in_pipeline():
    from repro import optimizers
    dims = [16, 16, 8, 16, 16]
    fixed = optimizers.kfac(MLP(dims, nonlin="tanh", loss="bernoulli"),
                            KFACConfig(use_rescale=False),
                            family="bernoulli")
    names = [s.name for s in _pipe(fixed).stages]
    assert "fused_precondition_momentum_clip" in names
    assert "precondition+quadratic_model_lr_momentum" not in names
    quad = optimizers.kfac(MLP(dims, nonlin="tanh", loss="bernoulli"),
                           KFACConfig(), family="bernoulli")
    names = [s.name for s in _pipe(quad).stages]
    assert "precondition+quadratic_model_lr_momentum" in names


def test_momentum_global_clip_matches_chained_form():
    params = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((7,))}
    u = {"a": jax.random.normal(jax.random.PRNGKey(0), (3, 4)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (7,))}
    fused = TF.momentum_global_clip(0.9, 0.5)
    ref = TF.chain(TF.with_momentum(0.9), TF.clip_by_global_norm(0.5))
    sf, sr = fused.init(params), ref.init(params)
    for i in range(5):
        uf, sf = fused.update(u, sf, params)
        ur, sr = ref.update(u, sr, params)
        for k in u:
            np.testing.assert_allclose(np.asarray(uf[k]), np.asarray(ur[k]),
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=f"step {i} leaf {k}")


def test_bad_autotune_mode_rejected():
    dims = [16, 16, 8, 16, 16]
    mlp = MLP(dims, nonlin="tanh", loss="bernoulli")
    with pytest.raises(ValueError, match="autotune"):
        KFACEngine(mlp, KFACConfig(autotune="sometimes"),
                   family="bernoulli")


# ---------------------------------------------------------------------------
# paged flash-decode tuning: legal KV-head blocks, every candidate allclose
# ---------------------------------------------------------------------------

PAGED_SHAPE = (2, 8, 2, 32, 3, 8)       # (b, hq, hkv, hd, max_blocks, page)


def test_paged_decode_candidates_legal():
    b, hq, hkv, hd, nb, page = PAGED_SHAPE
    cands = at.candidates("flash_decode_paged", PAGED_SHAPE)
    assert cands and {"hb": 1} in cands
    for cfg in cands:
        assert set(cfg) == {"hb"}
        assert cfg["hb"] <= hkv and hkv % cfg["hb"] == 0
    # ragged head dim / non-GQA head counts: no legal candidates
    assert at.candidates("flash_decode_paged", (2, 8, 2, 33, 3, 8)) == []
    assert at.candidates("flash_decode_paged", (2, 7, 2, 32, 3, 8)) == []


def test_paged_decode_every_candidate_allclose():
    """Each legal head block is the same kernel numerically — vs the
    dense-gather einsum oracle, not just vs another hb."""
    from repro.kernels import ops
    from repro.kernels.flash_decode import flash_decode_paged
    b, hq, hkv, hd, nb, page = PAGED_SHAPE
    num_pages = 1 + b * nb
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (b, hq, hd), jnp.float32)
    kp = jax.random.normal(jax.random.fold_in(k, 1),
                           (num_pages, hkv, page, hd), jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(k, 2),
                           (num_pages, hkv, page, hd), jnp.float32)
    pt = jax.random.permutation(jax.random.fold_in(k, 3),
                                jnp.arange(1, num_pages)).reshape(b, nb)
    lengths = jnp.asarray([page + 3, nb * page], jnp.int32)
    kd, vd = ops.paged_gather(kp, vp, pt)
    want = ops.flash_decode_ref(q, kd, vd, lengths, window=5, cap=30.0)
    for cfg in at.candidates("flash_decode_paged", PAGED_SHAPE):
        out = flash_decode_paged(q, kp, vp, lengths, pt, window=5, cap=30.0,
                                 interpret=True, **cfg)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"cfg={cfg}")


def test_paged_decode_tunes_and_caches():
    timer, calls = _counting_timer()
    cfg = at.tuned("flash_decode_paged", PAGED_SHAPE, jnp.bfloat16,
                   interpret=True, mode="cache", timer=timer)
    cands = at.candidates("flash_decode_paged", PAGED_SHAPE)
    assert cfg in cands
    assert calls["n"] == len(cands)
    at.clear_memo()                      # fresh process: disk hit
    assert at.tuned("flash_decode_paged", PAGED_SHAPE, jnp.bfloat16,
                    interpret=True, mode="cache", timer=timer) == cfg
    assert calls["n"] == len(cands)
