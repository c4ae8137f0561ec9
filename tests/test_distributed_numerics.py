"""Distributed *numerics* tests — run in subprocesses with 8 fake CPU
devices (the main test process must keep seeing 1 device; cf.
tests/test_distributed.py, which checks lowering only).

Covers the distributed curvature service end to end on a real multi-device
mesh: the sharded block-parallel refresh is bitwise-identical to the
serial one, the async overlap mode trains under its staleness bound, and
K-FAC state survives an elastic pod-count change (8 -> 4 devices)
bit-for-bit through ``remesh_plan`` + ``reshard``.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import optimizers
    from repro.configs.base import KFACConfig
    from repro.data.pipeline import SyntheticAutoencoderData
    from repro.models.mlp import MLP

    assert len(jax.devices()) == 8, jax.devices()

    def problem(dims=(32, 16, 8, 16, 32), n=256):
        mlp = MLP(list(dims), nonlin="tanh", loss="bernoulli")
        params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
        data = SyntheticAutoencoderData(dims[0], 6, n, seed=7)
        return mlp, params, data

    def run(cfg, steps=8):
        mlp, params, data = problem()
        opt = optimizers.kfac(mlp, cfg, family="bernoulli")
        state = opt.init(params, data.batch(0))
        hist = []
        for step in range(steps):
            b = data.batch(step)
            rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
            params, state, m = opt.update(None, state, params, b, rng)
            if opt.poll is not None:
                state = opt.poll(state)
            hist.append({k: float(v) for k, v in m.items()
                         if jnp.ndim(v) == 0})
        return params, state, hist

    def trees_equal(a, b, err=""):
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_array_equal(x, y, err_msg=err),
            a, b)
""")

_SHARDED_TAIL = textwrap.dedent("""
    cfg = KFACConfig(inv_mode="{inv_mode}", inverse_method="{method}",
                     lambda_init=1.0, t1=5, t2=4, t3=5, eta=1e-5)
    p1, s1, _ = run(cfg)
    p2, s2, _ = run(cfg.replace(refresh_mode="sharded"))
    trees_equal(p1, p2, "params")
    trees_equal(s1.inv, s2.inv, "inv")
    np.testing.assert_array_equal(s1.lam, s2.lam)
    # the refresh really is spread over the mesh: every loaded shard owns
    # strictly less than the whole cost
    from repro.distributed.refresh import build_sharded_refresh
    eng = optimizers.kfac(problem()[0], cfg, family="bernoulli").engine
    plan = build_sharded_refresh(eng).plan
    assert plan.n_shards == 8
    assert plan.parallel_cost() < plan.serial_cost()
    print("RESULT ok")
""")

_OVERLAP = _PRELUDE + textwrap.dedent("""
    cfg = KFACConfig(inv_mode="blkdiag", inverse_method="eigh",
                     lambda_init=1.0, t1=5, t2=0, t3=3, eta=1e-5,
                     refresh_mode="overlap")
    params, state, hist = run(cfg, steps=12)
    losses = [h["loss"] for h in hist]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    stale = [h.get("staleness", 0.0) for h in hist]
    assert max(stale) <= cfg.t3, stale
    assert state.inv_pending is not None
    print("RESULT ok")
""")

_ELASTIC = _PRELUDE + textwrap.dedent("""
    from repro.training.elastic import remesh_plan, reshard

    # an 8-device pod, FSDP(data=4) x TP(model=2)
    from repro.launch.mesh import make_mesh
    old_mesh = make_mesh((4, 2), ("data", "model"))
    cfg = KFACConfig(inv_mode="blkdiag", inverse_method="eigh",
                     lambda_init=1.0)
    mlp, params, data = problem()
    opt = optimizers.kfac(mlp, cfg, family="bernoulli")
    state = opt.init(params, data.batch(0))
    # populate every slot with real (non-symmetric-zero) values
    params, state, _ = opt.update(None, state, params, data.batch(0),
                                  jax.random.PRNGKey(1))

    rep = jax.sharding.NamedSharding(old_mesh, jax.sharding.PartitionSpec())
    param_sh = jax.tree.map(lambda _: rep, params)
    state_sh = opt.state_shardings(jax.eval_shape(lambda s: s, state),
                                   param_sh, old_mesh)
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
    state8 = reshard(state, state_sh)

    # the pod shrank: rebuild on 4 of the 8 hosts' devices, same logical
    # layout — remesh_plan maps the PartitionSpec tree onto the new mesh
    new_mesh = make_mesh((2, 2), ("data", "model"),
                         devices=jax.devices()[:4])
    specs = jax.tree.map(lambda sh: sh.spec, state_sh)
    new_sh = remesh_plan(old_mesh, new_mesh, specs)
    state4 = reshard(state8, new_sh)

    used = {d for leaf in jax.tree.leaves(state4)
            for d in leaf.sharding.device_set}
    assert used <= set(jax.devices()[:4]), used
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        state4, host)
    # ... and back up to 8 devices, still bitwise
    state_back = reshard(state4, state_sh)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        state_back, host)
    print("RESULT ok")
""")


def _run_script(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert any(l.startswith("RESULT ok") for l in out.stdout.splitlines()), \
        out.stdout[-2000:]


@pytest.mark.distributed
@pytest.mark.parametrize("inv_mode,method", [("blkdiag", "eigh"),
                                             ("blkdiag", "ns"),
                                             ("eigen", "eigh")])
def test_sharded_refresh_bitwise_on_8_devices(inv_mode, method):
    """Acceptance: on a forced 8-device CPU mesh, refresh_mode="sharded"
    produces params and inverses bitwise-identical to "serial"."""
    _run_script(_PRELUDE + _SHARDED_TAIL.format(inv_mode=inv_mode,
                                                method=method))


@pytest.mark.distributed
def test_overlap_refresh_on_8_devices():
    """Async double-buffered refresh on the real 8-device mesh: trains,
    stays finite, staleness bounded by T3."""
    _run_script(_OVERLAP)


@pytest.mark.distributed
def test_elastic_remesh_8_to_4_bitwise():
    """Pod-count change: sharded K-FAC state restores onto a 4-device
    mesh (and back) through remesh_plan + reshard without changing a
    single bit."""
    _run_script(_ELASTIC)
