"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.factor_update import factor_update
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.ns_step import ns_inverse, ns_step
from repro.kernels.precond import precondition


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    a, b = _rand(0, (m, k), dtype), _rand(1, (k, n), dtype)
    c = _rand(2, (m, n), jnp.float32)
    out = matmul(a, b, c, alpha=0.7, beta=0.3, bm=128, bn=128, bk=128)
    want = ref.matmul_ref(a, b, c, alpha=0.7, beta=0.3)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d", [(256, 128), (512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_factor_update(n, d, dtype):
    x = _rand(3, (n, d), dtype)
    c = _rand(4, (d, d), jnp.float32)
    out = factor_update(x, c, alpha=0.05, beta=0.95)
    want = ref.factor_update_ref(x, c, alpha=0.05, beta=0.95)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


def test_ns_step_matches_ref():
    d = 128
    m = _rand(5, (d, d), jnp.float32)
    m = m @ m.T / d + jnp.eye(d)
    x0 = jnp.eye(d) / jnp.max(jnp.sum(jnp.abs(m), -1))
    np.testing.assert_allclose(ns_step(m, x0), ref.ns_step_ref(m, x0),
                               rtol=1e-5, atol=1e-5)


def test_ns_inverse_converges():
    d = 128
    m = _rand(6, (d, d), jnp.float32)
    m = m @ m.T / d + jnp.eye(d)          # well-conditioned SPD
    inv = ns_inverse(m, iters=30)
    np.testing.assert_allclose(inv @ m, jnp.eye(d), rtol=0, atol=1e-3)


def test_precondition():
    d_in, d_out = 256, 128
    a = _rand(7, (d_in, d_in), jnp.float32)
    g = _rand(8, (d_out, d_out), jnp.float32)
    v = _rand(9, (d_in, d_out), jnp.float32)
    np.testing.assert_allclose(precondition(a, v, g),
                               ref.precondition_ref(a, v, g),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# fused im2col patch-factor kernel (KFC, 1602.01407)
# ---------------------------------------------------------------------------

def _patch_factor_ref(x, old, meta, alpha, beta):
    """Einsum oracle: explicit im2col + homogeneous coord + rank update."""
    from repro.models.conv import append_homog, extract_patches
    p = extract_patches(x, meta.conv_spatial, meta.conv_stride, meta.conv_pad)
    p = p.reshape(-1, p.shape[-1])
    if meta.has_bias:
        p = append_homog(p)
    return beta * old + alpha * p.T @ p


@pytest.mark.parametrize("b,t,c,k,stride,pad,bias", [
    (2, 128, 8, 3, 1, "SAME", True),      # whisper conv1 shape family
    (2, 256, 16, 3, 2, "SAME", True),     # whisper conv2 (stride 2)
    (1, 131, 8, 4, 1, "VALID", False),    # VALID with leftover rows
    (2, 512, 128, 3, 1, "SAME", True),    # full 128-lane channel tile
])
def test_patch_factor_kernel(b, t, c, k, stride, pad, bias):
    from repro.kernels.patch_factor import patch_factor_update
    from repro.models.conv import conv_meta
    meta = conv_meta("c", ("w",), spatial=(k,), stride=(stride,), c_in=c,
                     d_out=4, padding=pad, bias=bias)
    x = _rand(30, (b, t, c), jnp.float32)
    old = _rand(31, (meta.a_dim, meta.a_dim), jnp.float32)
    # traced alpha/beta through jit, like the optimizer's decayed blend
    got = jax.jit(lambda a, be: patch_factor_update(x, old, meta, a, be))(
        jnp.float32(0.03), jnp.float32(0.9))
    assert got is not None, "kernel unexpectedly declined a tiled shape"
    want = _patch_factor_ref(x, old, meta, 0.03, 0.9)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("c,t,k,pad", [
    (13, 128, 3, "SAME"),     # ragged channels
    (8, 21, 3, "SAME"),       # ragged output positions
    (136, 128, 3, "SAME"),    # channels over the 128-lane tile
    (8, 8, 9, "SAME"),        # taps exceed the time block (halo too short)
    (8, 2, 3, "VALID"),       # t < k: zero output positions
])
def test_patch_factor_ragged_declines(c, t, k, pad):
    """Shapes the kernel can't serve return None (never crash) — the block
    then falls back to the einsum path (parity checked in test_blocks)."""
    from repro.kernels.patch_factor import patch_factor_update
    from repro.models.conv import conv_meta
    meta = conv_meta("c", ("w",), spatial=(k,), stride=(1,), c_in=c,
                     d_out=4, padding=pad)
    x = _rand(32, (2, t, c), jnp.float32)
    old = jnp.eye(meta.a_dim)
    assert patch_factor_update(x, old, meta, 0.1, 0.9) is None


def test_patch_factor_2d_declines():
    """2-D convs decline the fused kernel (their im2col is a reshape; the
    plain factor_update kernel covers them via the block route)."""
    from repro.kernels.patch_factor import patch_factor_update
    from repro.models.conv import conv_meta
    meta = conv_meta("c", ("w",), spatial=(4, 4), stride=(4, 4), c_in=8,
                     d_out=4, padding="VALID")
    x = _rand(33, (2, 16, 16, 8), jnp.float32)
    assert patch_factor_update(x.reshape(2, 256, 8), jnp.eye(meta.a_dim),
                               meta, 0.1, 0.9) is None


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window,cap", [(True, 0, 0.0),
                                               (True, 64, 0.0),
                                               (True, 0, 30.0),
                                               (False, 0, 0.0)])
def test_flash_attention(hq, hkv, causal, window, cap):
    b, tq, tk, hd = 2, 128, 128, 64
    q = _rand(10, (b, hq, tq, hd), jnp.float32)
    k = _rand(11, (b, hkv, tk, hd), jnp.float32)
    v = _rand(12, (b, hkv, tk, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                          bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    b, hq, hkv, t, hd = 1, 2, 1, 128, 64
    q = _rand(13, (b, hq, t, hd), jnp.bfloat16)
    k = _rand(14, (b, hkv, t, hd), jnp.bfloat16)
    v = _rand(15, (b, hkv, t, hd), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("length", [1, 100, 512])
def test_flash_decode(length):
    from repro.kernels.flash_decode import flash_decode
    b, hq, hkv, s, hd = 2, 4, 2, 512, 64
    q = _rand(20, (b, hq, hd), jnp.float32)
    k = _rand(21, (b, hkv, s, hd), jnp.float32)
    v = _rand(22, (b, hkv, s, hd), jnp.float32)
    out = flash_decode(q, k, v, length, bk=128)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd)
    sc = jnp.einsum("bhgd,bhsd->bhgs", qg, k) / np.sqrt(hd)
    sc = jnp.where(jnp.arange(s) < length, sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    want = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(b, hq, hd)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,cap", [(0, 0.0), (130, 0.0), (0, 30.0),
                                        (96, 50.0)])
def test_flash_decode_per_row_lengths(window, cap):
    """(B,) length vector + sliding window + softcap vs the einsum oracle."""
    from repro.kernels.flash_decode import flash_decode
    b, hq, hkv, s, hd = 4, 4, 2, 384, 64
    q = _rand(30, (b, hq, hd), jnp.float32)
    k = _rand(31, (b, hkv, s, hd), jnp.float32)
    v = _rand(32, (b, hkv, s, hd), jnp.float32)
    lengths = jnp.asarray([1, 77, 200, 384], jnp.int32)
    out = flash_decode(q, k, v, lengths, bk=128, window=window, cap=cap)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd)
    sc = jnp.einsum("bhgd,bhsd->bhgs", qg, k) / np.sqrt(hd)
    if cap:
        sc = cap * jnp.tanh(sc / cap)
    pos = jnp.arange(s)
    valid = pos[None, :] < lengths[:, None]
    if window:
        valid &= pos[None, :] >= (lengths - window)[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    want = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(b, hq, hd)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    # the ops-level wrapper (einsum fallback on CPU) must agree too
    from repro.kernels import ops
    out2 = ops.flash_decode(q, k, v, lengths, window=window, cap=cap)
    np.testing.assert_allclose(out2, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged flash decode: differential parity vs the dense-gather einsum oracle
# ---------------------------------------------------------------------------

def _paged_case(page, nb, b=4, hq=4, hkv=2, hd=32):
    """Random pools + a shuffled non-contiguous page assignment (what the
    free list actually hands out after reuse) + ragged per-row lengths that
    straddle page boundaries (1, exactly one page, one past, mid-page)."""
    num_pages = 1 + b * nb
    q = _rand(40, (b, hq, hd), jnp.float32)
    kp = _rand(41, (num_pages, hkv, page, hd), jnp.float32)
    vp = _rand(42, (num_pages, hkv, page, hd), jnp.float32)
    pt = jax.random.permutation(jax.random.PRNGKey(43),
                                jnp.arange(1, num_pages)).reshape(b, nb)
    lengths = jnp.asarray([1, page, page + 1,
                           min(3 * page + 2, nb * page)], jnp.int32)[:b]
    return q, kp, vp, pt, lengths


@pytest.mark.parametrize("page", [4, 8, 16])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (6, 0.0), (0, 25.0),
                                        (5, 30.0)])
def test_flash_decode_paged_parity(page, window, cap):
    """Block-indexed paged kernel (page table as scalar-prefetch operand)
    vs gather-the-pages-then-einsum, across page sizes, boundary-straddling
    ragged lengths, sliding window, softcap and both KV-head blocks."""
    from repro.kernels import ops
    from repro.kernels.flash_decode import flash_decode_paged
    q, kp, vp, pt, lengths = _paged_case(page, nb=4)
    kd, vd = ops.paged_gather(kp, vp, pt)
    want = ops.flash_decode_ref(q, kd, vd, lengths, window=window, cap=cap)
    for hb in (1, 2):
        out = flash_decode_paged(q, kp, vp, lengths, pt, hb=hb,
                                 window=window, cap=cap, interpret=True)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"page={page} hb={hb}")


def test_flash_decode_paged_single_row_matches_batch():
    """B=1 vs full batch: each row of the batched paged kernel equals its
    own single-row call (rows are independent grid slices)."""
    from repro.kernels.flash_decode import flash_decode_paged
    q, kp, vp, pt, lengths = _paged_case(page=8, nb=3, b=3)
    full = flash_decode_paged(q, kp, vp, lengths, pt, interpret=True)
    for r in range(q.shape[0]):
        solo = flash_decode_paged(q[r:r + 1], kp, vp, lengths[r:r + 1],
                                  pt[r:r + 1], interpret=True)
        np.testing.assert_allclose(full[r], solo[0], rtol=1e-6, atol=1e-6)


def test_ops_flash_decode_paged_routes_agree():
    """The ops wrapper's XLA fallback (gather + einsum oracle) and its
    Pallas route must produce the same output for the same pools."""
    from repro.kernels import ops
    q, kp, vp, pt, lengths = _paged_case(page=8, nb=4)
    saved = dict(ops._STATE)
    try:
        ops.use_pallas(False)
        fallback = ops.flash_decode_paged(q, kp, vp, lengths, pt,
                                          window=5, cap=30.0)
        ops.use_pallas(True, interpret=True)
        kernel = ops.flash_decode_paged(q, kp, vp, lengths, pt,
                                        window=5, cap=30.0)
    finally:
        ops._STATE.update(saved)
    np.testing.assert_allclose(kernel, fallback, rtol=2e-5, atol=2e-5)
