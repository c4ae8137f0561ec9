"""Distributed lowering tests — run in a subprocess with 8 fake CPU devices
(the main test process must keep seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced_config
    from repro.configs.base import KFACConfig
    from repro.core.kfac import KFAC
    from repro.launch.specs import train_batch_specs, rng_spec
    from repro.launch import hlo_cost
    from repro.configs.base import ShapeConfig
    from repro.models.lm import LM

    arch = sys_arch = "{arch}"
    multi_pod = {multi_pod}
    from repro.launch.mesh import make_mesh
    mesh = (make_mesh((2, 2, 2), ("pod", "data", "model")) if multi_pod
            else make_mesh((4, 2), ("data", "model")))
    cfg = get_reduced_config(arch)
    shape = ShapeConfig("t", 32, 8, "train")
    kcfg = KFACConfig(max_factor_dim=64, inv_mode="{inv_mode}")
    lm = LM(cfg, kcfg, mesh, compute_dtype=jnp.bfloat16)
    opt = KFAC(lm, kcfg, mesh)
    params_abs = lm.abstract_params(jnp.float32)
    batch_abs = train_batch_specs(cfg, shape, mesh)
    state_abs = jax.eval_shape(opt.init, params_abs, batch_abs)
    state_sh = opt.state_shardings(state_abs, lm.param_shardings(), mesh)
    state_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_abs, state_sh)

    def train_step(state, params, batch, rng):
        state, grads, metrics = opt.stats_grads(state, params, batch, rng)
        state = opt.rescale_step(state, grads)   # no-op unless inv_mode=eigen
        params, state, um = opt.apply_update(state, params, grads, batch, rng)
        return params, state

    with mesh:
        lowered = jax.jit(train_step).lower(state_abs, params_abs, batch_abs,
                                            rng_spec(mesh))
        compiled = lowered.compile()
    res = hlo_cost.analyze(compiled.as_text())
    ag = [hlo_cost.shape_bytes(k) for k in res["top_collectives"]
          if k.startswith("all-gather")]
    print("RESULT" + json.dumps({{
        "flops": res["flops"], "coll": res["collectives"]["total"],
        "max_allgather": max(ag) if ag else 0,
        "n_devices": len(jax.devices())}}))
""")


def _run(arch: str, multi_pod: bool, inv_mode: str = "blkdiag"):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT.format(arch=arch, multi_pod=multi_pod, inv_mode=inv_mode)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.distributed
@pytest.mark.parametrize("arch", ["llama3.2-1b", "granite-moe-1b-a400m"])
def test_single_pod_lowering(arch):
    res = _run(arch, multi_pod=False)
    assert res["n_devices"] == 8
    assert res["flops"] > 0


@pytest.mark.distributed
def test_multi_pod_lowering():
    res = _run("llama3.2-1b", multi_pod=True)
    assert res["n_devices"] == 8
    assert res["flops"] > 0


@pytest.mark.distributed
def test_eigen_mode_lowering():
    """inv_mode="eigen": eigen state shardings resolve (None entries pair
    with identity bases), stats→rescale→update lowers on the 8-device fake
    mesh, and no collective all-gathers a full eigenbasis — the rotations
    run against the local shards (hlo_cost's biggest all-gather site stays
    far below the largest (d, d) basis)."""
    res = _run("llama3.2-1b", multi_pod=False, inv_mode="eigen")
    assert res["n_devices"] == 8
    assert res["flops"] > 0
    assert res["coll"] > 0           # grad reductions must exist
    # per-instance gather bound: the FSDP weight-tile gathers in this
    # lowering are <= 32 KiB, while any stacked eigenbasis or eigenbasis
    # diagonal (e.g. the embed (256, 64) s, or a scanned (2, 2, 64, 64)
    # qa) is >= 64 KiB — gathering one would trip this (0 gathers is fine)
    assert res["max_allgather"] < 64 * 1024, res["max_allgather"]
