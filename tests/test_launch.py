"""Entry points: the compile cache they share, the launcher's meshes, the
chip smoke's refusal to run without a TPU, and imports that leave the
device alone."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.utils import compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _env(**kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(kw)
    return env


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    want = os.path.realpath(os.path.join(ROOT, ".jax_cache"))
    assert os.path.realpath(got) == want
    assert os.path.realpath(jax.config.jax_compilation_cache_dir) == want


def test_compile_cache_env_wins(monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets nothing over it
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_lands_only_in_the_env_dir(tmp_path):
    """A compiled program is written under JAX_COMPILATION_CACHE_DIR and
    nowhere else (the checkout's .jax_cache gains nothing)."""
    before = set(os.listdir(compile_cache.CHECKOUT_CACHE)) \
        if compile_cache.CHECKOUT_CACHE.exists() else set()
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.utils.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.listdir(tmp_path)
    after = set(os.listdir(compile_cache.CHECKOUT_CACHE)) \
        if compile_cache.CHECKOUT_CACHE.exists() else set()
    assert after == before


def test_imports_initialise_no_backend():
    """Importing the model, the kernels and the launchers must not take
    the chip: a parent that touched the backend would hold it."""
    code = ("import repro.models.lm, repro.kernels.ops, repro.launch.train, "
            "repro.launch.serve\n"
            "from jax._src import xla_bridge as xb\n"
            "print(xb.backends_are_initialized())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=_env())
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_chip_smoke_refuses_without_a_tpu():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=_env())
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
    assert "no TPU" in out.stderr


def test_train_launcher_local_mesh(capsys):
    """--mesh local builds an Auto-axis mesh and trains through it."""
    from repro.launch import train
    res = train.main(["--arch", "smollm-135m", "--reduced", "--steps", "2",
                      "--global_batch", "2", "--seq", "16", "--mesh",
                      "local"])
    assert len(res["history"]) == 2
    assert "[train] done" in capsys.readouterr().out


def test_meshes_have_auto_axes():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_local_mesh, make_mesh
    for mesh in (make_local_mesh(),
                 make_mesh((1,), ("shard",), devices=jax.devices()[:1])):
        assert set(mesh.axis_types) == {AxisType.Auto}
