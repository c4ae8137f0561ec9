"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests check what the kernels compute; they cannot see what
the TPU compiler refuses (block shapes that do not tile, unsupported
primitives).  Each case here lowers a kernel with ``interpret=False`` for
one chip of a described ``v5e:2x2`` topology and compiles it ahead of
time: no chip is needed, only the TPU compiler that ships with JAX.  The
topology is described inside a module fixture, never at import, so every
test worker collects the same tests; where it cannot be described, the
tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import factor_update as fu
from repro.kernels import flash_decode as fd
from repro.kernels import patch_factor as pf
from repro.kernels import precond as pc
from repro.kernels import rotate_rescale as rr
from repro.kernels import update_chain as uc

# smollm-135m serving: 8 slots, 9 query / 3 KV heads of 64, pages of 8
# positions, 320 + 32 positions per slot
SLOTS, HQ, HKV, HD, PAGE, BLOCKS = 8, 9, 3, 64, 8, 44
POOL = (1 + SLOTS * BLOCKS, HKV, PAGE, HD)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # AOT executables for a described chip cannot be read back from the
    # persistent cache, so keep it off while these compile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases():
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    return {
        "factor_update": (
            lambda x, c: fu.factor_update(x, c, alpha=0.1, beta=0.9,
                                          interpret=False),
            [((2048, 1536), f32), ((1536, 1536), f32)]),
        "precond": (
            lambda a, v, g: pc.precondition(a, v, g, interpret=False),
            [((1536, 1536), f32), ((1536, 512), f32), ((512, 512), f32)]),
        "rotate_rescale": (
            lambda qa, v, qg, s: rr.rotate_rescale(qa, v, qg, s, lam=1e-6,
                                                   interpret=False),
            [((1536, 1536), f32), ((1536, 512), f32), ((512, 512), f32),
             ((1536, 512), f32)]),
        "update_chain": (
            lambda a, v, g, m: uc.precond_momentum(a, v, g, m, alpha=-0.1,
                                                   mu=0.9, interpret=False),
            [((1536, 1536), f32), ((1536, 512), f32), ((512, 512), f32),
             ((1536, 512), f32)]),
        "patch_factor_stride1": (
            lambda x, c: pf.patch_factor(x, c, taps=3, stride=1, t_out=1024,
                                         alpha=0.1, beta=0.9,
                                         interpret=False),
            [((4, 1026, 128), f32), ((384, 384), f32)]),
        "patch_factor_stride2": (
            lambda x, c: pf.patch_factor(x, c, taps=3, stride=2, t_out=512,
                                         alpha=0.1, beta=0.9,
                                         interpret=False),
            [((4, 1025, 128), f32), ((384, 384), f32)]),
        "flash_decode": (
            lambda q, k, v, n: fd.flash_decode(q, k, v, n, interpret=False),
            [((SLOTS, HQ, HD), bf16), ((SLOTS, HKV, 384, HD), bf16),
             ((SLOTS, HKV, 384, HD), bf16), ((SLOTS,), i32)]),
        "flash_decode_paged_hb1": (
            lambda q, k, v, n, pt: fd.flash_decode_paged(
                q, k, v, n, pt, hb=1, interpret=False),
            [((SLOTS, HQ, HD), bf16), (POOL, bf16), (POOL, bf16),
             ((SLOTS,), i32), ((SLOTS, BLOCKS), i32)]),
        "flash_decode_paged_hb3": (
            lambda q, k, v, n, pt: fd.flash_decode_paged(
                q, k, v, n, pt, hb=3, window=64, cap=30.0,
                interpret=False),
            [((SLOTS, HQ, HD), bf16), (POOL, bf16), (POOL, bf16),
             ((SLOTS,), i32), ((SLOTS, BLOCKS), i32)]),
    }


@pytest.mark.parametrize("kernel", sorted(_cases()))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, specs = _cases()[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_patch_shapes_tile():
    """The patch cases above are shapes the conv block routes onto the
    kernel (the fused route declines the rest to einsum)."""
    assert pf.patch_tile_ok(128, 1024, 3, 1)
    assert pf.patch_tile_ok(128, 512, 3, 2)
