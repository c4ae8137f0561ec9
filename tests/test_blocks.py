"""Curvature-block registry + per-block correctness vs dense references.

Each CurvatureBlock subclass's precondition is checked against the dense
``(Ā ⊗ G)⁻¹ vec(V)`` of the same damped factors, and the Pallas-routed
paths (``kernel_backend="pallas"``, interpret mode on CPU) are checked to
agree with the ``"xla"`` einsum paths to tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import KFACConfig
from repro.core import blocks as B
from repro.core import factors as F
from repro.core.tags import LayerMeta

CFG = KFACConfig()
CFG_PALLAS = CFG.replace(kernel_backend="pallas")


def _spd(key, d, scale=1.0):
    m = jax.random.normal(jax.random.PRNGKey(key), (d, d))
    return m @ m.T / d * scale + 0.1 * jnp.eye(d)


def _dense_kron_reference(block, a_dense, g_dense, gamma, v):
    """(Ā ⊗ G)⁻¹ vec(V) with the block's own factored Tikhonov damping."""
    from repro.core import inverse as INV
    m = block.meta
    pi = INV.pi_trace(a_dense, "full", m.a_dim, g_dense, "full", m.g_dim)
    a_d = a_dense + pi * gamma * jnp.eye(m.a_dim)
    g_d = g_dense + gamma / pi * jnp.eye(m.g_dim)
    f = jnp.kron(a_d, g_d)
    return (jnp.linalg.solve(f, v.reshape(-1))).reshape(m.a_dim, m.g_dim)


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------

def _meta(kind="dense", a_kind="full", g_kind="full", a_blocks=1, g_blocks=1,
          d_in=6, d_out=4, **kw):
    return LayerMeta("l", ("w",), d_in=d_in, d_out=d_out, kind=kind,
                     a_kind=a_kind, g_kind=g_kind, a_blocks=a_blocks,
                     g_blocks=g_blocks, **kw)


@pytest.mark.parametrize("meta,cls", [
    (_meta(), B.DenseKronecker),
    (_meta(a_kind="block", a_blocks=2), B.BlockDiagKronecker),
    (_meta(g_kind="block", g_blocks=2), B.BlockDiagKronecker),
    (_meta(a_kind="diag"), B.DiagFactor),
    (_meta(a_kind="diag", g_kind="block", g_blocks=2), B.DiagFactor),
    (_meta(kind="embed", a_kind="diag"), B.Embed),
    (_meta(kind="head", g_kind="diag"), B.Head),
    (_meta(kind="expert", n_expert=3), B.Expert),
])
def test_registry_resolution(meta, cls):
    assert B.resolve(meta) is cls


def test_registry_unknown_kind():
    with pytest.raises(KeyError):
        B.resolve(_meta(kind="nope"))


def test_build_blocks_covers_all_metas():
    metas = {"x": _meta(), "e": _meta(kind="embed", a_kind="diag")}
    blocks = B.build_blocks(metas, CFG)
    assert set(blocks) == {"x", "e"}
    assert isinstance(blocks["x"], B.DenseKronecker)


# ---------------------------------------------------------------------------
# per-block precondition vs the dense (Ā ⊗ G)⁻¹ reference
# ---------------------------------------------------------------------------

def test_dense_kron_block_matches_dense_reference():
    meta = _meta(d_in=6, d_out=4)
    blk = B.resolve(meta)(meta, CFG)
    a, g = _spd(0, meta.a_dim), _spd(1, meta.g_dim)
    inv = blk.damped_inverse({"a": a, "g": g}, 0.3, method="eigh")
    v = jax.random.normal(jax.random.PRNGKey(2), (meta.a_dim, meta.g_dim))
    got = blk.precondition(inv, v)
    want = _dense_kron_reference(blk, a, g, 0.3, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_blockdiag_kron_matches_dense_reference():
    """A TP-blocked Ā equals a block-diagonal dense Ā."""
    meta = _meta(d_in=8, d_out=4, a_kind="block", a_blocks=2)
    blk = B.resolve(meta)(meta, CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, meta.a_dim))
    a_blk = F.outer_sum(x, "block", 2) / 32
    g = _spd(4, meta.g_dim)
    inv = blk.damped_inverse({"a": a_blk, "g": g}, 0.5, method="eigh")
    v = jax.random.normal(jax.random.PRNGKey(5), (meta.a_dim, meta.g_dim))
    got = blk.precondition(inv, v)

    # dense reference with the same damping: assemble block-diagonal Ā and
    # reuse the dense meta so pi matches the blocked trace exactly
    a_dense = jnp.zeros((meta.a_dim, meta.a_dim))
    for b in range(2):
        sl = slice(b * 4, (b + 1) * 4)
        a_dense = a_dense.at[sl, sl].set(a_blk[b])
    ref_meta = _meta(d_in=8, d_out=4)
    ref = B.resolve(ref_meta)(ref_meta, CFG)
    want = _dense_kron_reference(ref, a_dense, g, 0.5, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_diag_factor_matches_dense_reference():
    meta = _meta(d_in=5, d_out=4, a_kind="diag")
    blk = B.resolve(meta)(meta, CFG)
    a_diag = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                       (meta.a_dim,))) + 0.5
    g = _spd(7, meta.g_dim)
    inv = blk.damped_inverse({"a": a_diag, "g": g}, 0.4, method="eigh")
    v = jax.random.normal(jax.random.PRNGKey(8), (meta.a_dim, meta.g_dim))
    got = blk.precondition(inv, v)
    ref_meta = _meta(d_in=5, d_out=4)
    ref = B.resolve(ref_meta)(ref_meta, CFG)
    want = _dense_kron_reference(ref, jnp.diag(a_diag), g, 0.4, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_embed_head_blocks_match_dense_reference():
    for kind, a_kind, g_kind in (("embed", "diag", "full"),
                                 ("head", "full", "diag")):
        meta = _meta(kind=kind, d_in=7, d_out=3, a_kind=a_kind, g_kind=g_kind)
        blk = B.resolve(meta)(meta, CFG)
        if a_kind == "diag":
            a = jnp.abs(jax.random.normal(jax.random.PRNGKey(9),
                                          (meta.a_dim,))) + 0.5
            g = _spd(10, meta.g_dim)
            a_dense, g_dense = jnp.diag(a), g
        else:
            a = _spd(11, meta.a_dim)
            g = jnp.abs(jax.random.normal(jax.random.PRNGKey(12),
                                          (meta.g_dim,))) + 0.5
            a_dense, g_dense = a, jnp.diag(g)
        inv = blk.damped_inverse({"a": a, "g": g}, 0.2, method="eigh")
        v = jax.random.normal(jax.random.PRNGKey(13),
                              (meta.a_dim, meta.g_dim))
        got = blk.precondition(inv, v)
        ref_meta = _meta(d_in=7, d_out=3)
        ref = B.resolve(ref_meta)(ref_meta, CFG)
        want = _dense_kron_reference(ref, a_dense, g_dense, 0.2, v)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5,
                                   err_msg=kind)


def test_expert_block_matches_per_expert_dense():
    ne = 3
    meta = _meta(kind="expert", d_in=5, d_out=4, n_expert=ne)
    blk = B.resolve(meta)(meta, CFG)
    a = jnp.stack([_spd(20 + e, meta.a_dim) for e in range(ne)])
    g = jnp.stack([_spd(30 + e, meta.g_dim) for e in range(ne)])
    inv = blk.damped_inverse({"a": a, "g": g}, 0.3, method="eigh")
    v = jax.random.normal(jax.random.PRNGKey(14),
                          (ne, meta.a_dim, meta.g_dim))
    got = blk.precondition(inv, v)
    ref_meta = _meta(d_in=5, d_out=4)
    ref = B.resolve(ref_meta)(ref_meta, CFG)
    for e in range(ne):
        want = _dense_kron_reference(ref, a[e], g[e], 0.3, v[e])
        np.testing.assert_allclose(got[e], want, rtol=1e-4, atol=1e-5,
                                   err_msg=f"expert {e}")


# ---------------------------------------------------------------------------
# kernel_backend="pallas" (interpret) vs "xla" agreement
# ---------------------------------------------------------------------------

def test_dense_update_factors_pallas_matches_xla():
    meta = _meta(d_in=64, d_out=32)
    n = 128
    a_raw = jax.random.normal(jax.random.PRNGKey(15), (n, meta.a_dim))
    cot = jax.random.normal(jax.random.PRNGKey(16), (n, meta.g_dim)) / n
    old = {"a": _spd(17, meta.a_dim), "g": _spd(18, meta.g_dim)}
    rec = {"a": a_raw}

    out = {}
    for label, cfg in (("xla", CFG), ("pallas", CFG_PALLAS)):
        blk = B.resolve(meta)(meta, cfg)
        # eps traced through jit, like the optimizer's decayed blend
        fn = jax.jit(lambda eps, b=blk: b.update_factors(
            old, rec, cot, {}, n, eps))
        out[label] = fn(jnp.float32(0.9))
    np.testing.assert_allclose(out["pallas"]["a"], out["xla"]["a"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out["pallas"]["g"], out["xla"]["g"],
                               rtol=1e-4, atol=1e-5)


def test_dense_update_factors_pallas_ragged_falls_back():
    """Non-tileable dims must still produce the einsum-path result."""
    meta = _meta(d_in=13, d_out=9)       # ragged: no 8-alignment
    n = 21
    rec = {"a": jax.random.normal(jax.random.PRNGKey(19), (n, meta.a_dim))}
    cot = jax.random.normal(jax.random.PRNGKey(20), (n, meta.g_dim)) / n
    old = {"a": _spd(21, meta.a_dim), "g": _spd(22, meta.g_dim)}
    blk_x = B.resolve(meta)(meta, CFG)
    blk_p = B.resolve(meta)(meta, CFG_PALLAS)
    want = blk_x.update_factors(old, rec, cot, {}, n, jnp.float32(0.8))
    got = blk_p.update_factors(old, rec, cot, {}, n, jnp.float32(0.8))
    np.testing.assert_allclose(got["a"], want["a"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["g"], want["g"], rtol=1e-5, atol=1e-6)


def test_stacked_update_factors_pallas_matches_xla():
    """Stacked (scanned) layers map the factor_update kernel over the stack
    on the G side; the A side, contracted in-forward ("aa"), stays einsum.
    Both sides must equal the einsum path, and the routes say so."""
    meta = _meta(d_in=32, d_out=16, n_stack=3)
    n = 64
    xa = jax.random.normal(jax.random.PRNGKey(30), (3, n, meta.a_dim))
    rec = {"aa": jnp.einsum("snd,sne->sde", xa, xa)}
    cot = jax.random.normal(jax.random.PRNGKey(31), (3, n, meta.g_dim)) / n
    old = {"a": jnp.stack([_spd(32 + i, meta.a_dim) for i in range(3)]),
           "g": jnp.stack([_spd(35 + i, meta.g_dim) for i in range(3)])}
    blk_x = B.resolve(meta)(meta, CFG)
    blk_p = B.resolve(meta)(meta, CFG_PALLAS)
    want = blk_x.update_factors(old, rec, cot, {}, n, jnp.float32(0.9))
    got = jax.jit(lambda eps: blk_p.update_factors(old, rec, cot, {}, n,
                                                   eps))(jnp.float32(0.9))
    np.testing.assert_allclose(got["a"], want["a"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["g"], want["g"], rtol=1e-4, atol=1e-5)
    assert blk_p.routes == {"factor_update.a": "einsum",
                            "factor_update.g": "pallas"}
    assert B.route_counts({"x": blk_x, "p": blk_p}) == {
        "factor_update.a": {"pallas": 0, "einsum": 2},
        "factor_update.g": {"pallas": 1, "einsum": 1}}


@pytest.mark.parametrize("d_in,d_out,route", [(64, 32, "pallas"),
                                              (13, 9, "einsum")])
def test_precondition_route_is_recorded(d_in, d_out, route):
    """A shape that does not tile declines to einsum — and says so."""
    meta = _meta(d_in=d_in, d_out=d_out)
    blk = B.resolve(meta)(meta, CFG_PALLAS)
    inv = {"a_inv": _spd(40, meta.a_dim), "g_inv": _spd(41, meta.g_dim)}
    v = jax.random.normal(jax.random.PRNGKey(42), (meta.a_dim, meta.g_dim))
    blk.precondition(inv, v)
    assert blk.routes == {"precond": route}


def test_dense_precondition_pallas_matches_xla():
    meta = _meta(d_in=64, d_out=32)
    a, g = _spd(23, meta.a_dim), _spd(24, meta.g_dim)
    v = jax.random.normal(jax.random.PRNGKey(25), (meta.a_dim, meta.g_dim))
    blk_x = B.resolve(meta)(meta, CFG)
    blk_p = B.resolve(meta)(meta, CFG_PALLAS)
    inv = blk_x.damped_inverse({"a": a, "g": g}, 0.3, method="eigh")
    want = blk_x.precondition(inv, v)
    got = jax.jit(blk_p.precondition)(inv, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_dense_precondition_pallas_stacked_vmaps():
    """Scan-stacked layers route through the kernel via vmap."""
    ns = 3
    meta = _meta(d_in=32, d_out=16, n_stack=ns)
    a = jnp.stack([_spd(40 + i, meta.a_dim) for i in range(ns)])
    g = jnp.stack([_spd(50 + i, meta.g_dim) for i in range(ns)])
    v = jax.random.normal(jax.random.PRNGKey(26),
                          (ns, meta.a_dim, meta.g_dim))
    blk_x = B.resolve(meta)(meta, CFG)
    blk_p = B.resolve(meta)(meta, CFG_PALLAS)
    inv = blk_x.damped_inverse({"a": a, "g": g}, 0.4, method="eigh")
    want = blk_x.precondition(inv, v)
    got = blk_p.precondition(inv, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# eigen (EKFAC) path: eigen_state right after a refresh must reproduce the
# eigh damped-inverse apply; rotate_rescale kernel must match the einsum path
# ---------------------------------------------------------------------------

CFG_EIGEN = KFACConfig(inv_mode="eigen")


def _eigen_factors(meta, seed):
    """SPD/positive factors matching the meta's per-side kinds."""
    def one(dim, kind, blocks, s):
        if kind == "diag":
            return jnp.abs(jax.random.normal(jax.random.PRNGKey(s),
                                             (dim,))) + 0.5
        if kind == "block":
            x = jax.random.normal(jax.random.PRNGKey(s), (4 * dim, dim))
            return F.outer_sum(x, "block", blocks) / (4 * dim)
        if meta.n_expert:
            return jnp.stack([_spd(s + e, dim) for e in range(meta.n_expert)])
        return _spd(s, dim)

    return {"a": one(meta.a_dim, meta.a_kind, meta.a_blocks, seed),
            "g": one(meta.g_dim, meta.g_kind, meta.g_blocks, seed + 1)}


@pytest.mark.parametrize("meta", [
    _meta(d_in=6, d_out=4),
    _meta(d_in=8, d_out=4, a_kind="block", a_blocks=2),
    _meta(d_in=5, d_out=4, a_kind="diag"),
    _meta(kind="embed", d_in=7, d_out=3, a_kind="diag"),
    _meta(kind="head", d_in=6, d_out=3, g_kind="diag"),
    _meta(kind="expert", d_in=5, d_out=4, n_expert=3),
], ids=["dense", "blockdiag", "diagfactor", "embed", "head", "expert"])
def test_eigen_state_matches_eigh_preconditioner(meta):
    """With s initialized from the exact factor eigenvalues (what
    eigen_state does at refresh), the eigenbasis apply IS the eigh path."""
    blk = B.resolve(meta)(meta, CFG_EIGEN)
    fac = _eigen_factors(meta, 60)
    gamma = 0.3
    inv = blk.damped_inverse(fac, gamma, method="eigh")
    eig = blk.eigen_state(fac, gamma)
    shape = ((meta.n_expert,) if meta.n_expert else ()) + (meta.a_dim,
                                                           meta.g_dim)
    v = jax.random.normal(jax.random.PRNGKey(62), shape)
    np.testing.assert_allclose(blk.precondition_eigen(eig, v),
                               blk.precondition(inv, v),
                               rtol=1e-4, atol=1e-4)


def test_eigen_rescale_tracks_rotated_gradient():
    """eps=0 replaces s with the squared eigenbasis-rotated gradient; the
    blend interpolates linearly in between."""
    from repro.core import inverse as INV
    meta = _meta(d_in=6, d_out=4)
    blk = B.resolve(meta)(meta, CFG_EIGEN)
    eig = blk.eigen_state(_eigen_factors(meta, 70), 0.2)
    g = jax.random.normal(jax.random.PRNGKey(71), (meta.a_dim, meta.g_dim))
    t2 = jnp.square(INV.rotate_eigen(meta, eig["qa"], eig["qg"], g,
                                     adjoint=True))
    e0 = blk.rescale_step(eig, g, jnp.float32(0.0))
    np.testing.assert_allclose(e0["s"], t2, rtol=1e-5, atol=1e-6)
    e_half = blk.rescale_step(eig, g, jnp.float32(0.5))
    np.testing.assert_allclose(e_half["s"], 0.5 * eig["s"] + 0.5 * t2,
                               rtol=1e-5, atol=1e-6)
    # bases and the amortized damping are untouched by the per-step update
    np.testing.assert_allclose(e0["qa"], eig["qa"])
    np.testing.assert_allclose(e0["damp"], eig["damp"])


def test_eigen_rotation_is_orthogonal():
    """Rotating in and straight back out is the identity (Q orthonormal)."""
    from repro.core import inverse as INV
    meta = _meta(d_in=8, d_out=4, a_kind="block", a_blocks=2)
    blk = B.resolve(meta)(meta, CFG_EIGEN)
    eig = blk.eigen_state(_eigen_factors(meta, 80), 0.1)
    v = jax.random.normal(jax.random.PRNGKey(81), (meta.a_dim, meta.g_dim))
    t = INV.rotate_eigen(meta, eig["qa"], eig["qg"], v, adjoint=True)
    back = INV.rotate_eigen(meta, eig["qa"], eig["qg"], t, adjoint=False)
    np.testing.assert_allclose(back, v, rtol=1e-5, atol=1e-5)


def test_rotate_rescale_pallas_matches_xla():
    """Acceptance: pallas vs xla eigen apply agree to <= 1e-5."""
    meta = _meta(d_in=64, d_out=32)
    blk_x = B.resolve(meta)(meta, CFG_EIGEN)
    blk_p = B.resolve(meta)(meta, CFG_EIGEN.replace(kernel_backend="pallas"))
    eig = blk_x.eigen_state(_eigen_factors(meta, 90), 0.3)
    v = jax.random.normal(jax.random.PRNGKey(91), (meta.a_dim, meta.g_dim))
    want = blk_x.precondition_eigen(eig, v)
    got = jax.jit(blk_p.precondition_eigen)(eig, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rotate_rescale_pallas_stacked_vmaps():
    ns = 3
    meta = _meta(d_in=32, d_out=16, n_stack=ns)
    blk_x = B.resolve(meta)(meta, CFG_EIGEN)
    blk_p = B.resolve(meta)(meta, CFG_EIGEN.replace(kernel_backend="pallas"))
    a = jnp.stack([_spd(100 + i, meta.a_dim) for i in range(ns)])
    g = jnp.stack([_spd(110 + i, meta.g_dim) for i in range(ns)])
    eig = blk_x.eigen_state({"a": a, "g": g}, 0.4)
    v = jax.random.normal(jax.random.PRNGKey(112),
                          (ns, meta.a_dim, meta.g_dim))
    want = blk_x.precondition_eigen(eig, v)
    got = blk_p.precondition_eigen(eig, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rotate_rescale_pallas_ragged_falls_back():
    meta = _meta(d_in=13, d_out=9)        # no 8-alignment: einsum fallback
    blk_x = B.resolve(meta)(meta, CFG_EIGEN)
    blk_p = B.resolve(meta)(meta, CFG_EIGEN.replace(kernel_backend="pallas"))
    eig = blk_x.eigen_state(_eigen_factors(meta, 120), 0.2)
    v = jax.random.normal(jax.random.PRNGKey(121), (meta.a_dim, meta.g_dim))
    np.testing.assert_allclose(blk_p.precondition_eigen(eig, v),
                               blk_x.precondition_eigen(eig, v),
                               rtol=1e-6, atol=1e-7)


def test_kfac_eigen_step_end_to_end():
    """inv_mode="eigen" runs the full stats -> refresh -> rescale -> update
    cycle and the first post-refresh update matches inv_mode="blkdiag" with
    method="eigh" (identical preconditioner before any diagonal blending)."""
    from repro.core.kfac import KFAC
    from repro.models.mlp import MLP

    dims = [8, 16, 8]
    mlp = MLP(dims, loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    x = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (64, dims[0])
                             ).astype(jnp.float32)
    batch = {"x": x, "y": x}
    rng = jax.random.PRNGKey(2)

    results = {}
    for mode in ("blkdiag", "eigen"):
        cfg = KFACConfig(inv_mode=mode, inverse_method="eigh", t1=0, t2=0)
        opt = KFAC(mlp, cfg)
        state = opt.init(params, batch)
        state, grads, _ = jax.jit(opt.stats_grads)(state, params, batch, rng)
        state = jax.jit(opt.refresh_inverses)(state)
        new_params, state, _ = jax.jit(opt.apply_update)(
            state, params, grads, batch, rng)
        results[mode] = new_params
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4),
        results["eigen"], results["blkdiag"])


# ---------------------------------------------------------------------------
# ConvKronecker (KFC, 1602.01407): registry, dense-reference correctness,
# pallas==xla parity for the patch factor-update and precondition routes
# ---------------------------------------------------------------------------

def _conv_meta(c=8, k=3, stride=1, d_out=4, pad="SAME", bias=True, nd=1):
    from repro.models.conv import conv_meta
    return conv_meta("c", ("w",), spatial=(k,) * nd, stride=(stride,) * nd,
                     c_in=c, d_out=d_out, padding=pad, bias=bias)


def test_registry_resolves_conv():
    assert B.resolve(_conv_meta()) is B.ConvKronecker
    assert B.resolve(_conv_meta(nd=2)) is B.ConvKronecker


def test_conv_block_matches_dense_reference():
    """A ConvKronecker block's damped precondition equals the dense
    (Ā ⊗ G)⁻¹ reference on factors built from real patch statistics."""
    meta = _conv_meta(c=3, k=2, d_out=4)
    blk = B.resolve(meta)(meta, CFG)
    x = jax.random.normal(jax.random.PRNGKey(130), (4, 12, 3))
    cot = jax.random.normal(jax.random.PRNGKey(131), (4, 12, 4)) / 48
    fac = blk.stats_contrib({"cx": x}, cot, {}, 48)
    fac = {"a": fac["a"] + 0.1 * jnp.eye(meta.a_dim),
           "g": fac["g"] + 0.1 * jnp.eye(meta.g_dim)}
    inv = blk.damped_inverse(fac, 0.3, method="eigh")
    v = jax.random.normal(jax.random.PRNGKey(132), (meta.a_dim, meta.g_dim))
    got = blk.precondition(inv, v)
    ref_meta = _meta(d_in=meta.a_dim - 1, d_out=meta.g_dim, has_bias=True)
    ref = B.resolve(ref_meta)(ref_meta, CFG)
    want = _dense_kron_reference(ref, fac["a"], fac["g"], 0.3, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_conv_stats_match_dense_over_patches():
    """Feeding the raw input to ConvKronecker equals feeding the extracted
    (homogeneous) patches to a dense block — the KFC reduction."""
    from repro.models.conv import append_homog, extract_patches
    meta = _conv_meta(c=3, k=3, stride=2, d_out=4)
    blk = B.resolve(meta)(meta, CFG)
    x = jax.random.normal(jax.random.PRNGKey(133), (2, 15, 3))
    cot = jax.random.normal(jax.random.PRNGKey(134), (2, 8, 4)) / 16
    got = blk.stats_contrib({"cx": x}, cot, {}, 16)
    p = append_homog(extract_patches(x, (3,), (2,), "SAME"))
    dmeta = _meta(d_in=meta.a_dim, d_out=4)
    dense = B.resolve(dmeta)(dmeta, CFG)
    want = dense.stats_contrib({"a": p}, cot, {}, 16)
    np.testing.assert_allclose(got["a"], want["a"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["g"], want["g"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("meta,xshape", [
    (_conv_meta(c=8, k=3, stride=1), (2, 128, 8)),          # fused 1-D route
    (_conv_meta(c=16, k=3, stride=2), (2, 256, 16)),        # strided 1-D
    (_conv_meta(c=8, k=4, stride=4, pad="VALID", bias=False, nd=2),
     (2, 16, 16, 8)),                                       # 2-D patchify
    (_conv_meta(c=5, k=3, stride=1), (2, 21, 5)),           # ragged fallback
], ids=["conv1d", "conv1d_s2", "patchify2d", "ragged"])
def test_conv_update_factors_pallas_matches_xla(meta, xshape):
    rec = {"cx": jax.random.normal(jax.random.PRNGKey(135), xshape)}
    n = 64
    t_out = B.resolve(meta)(meta, CFG).patches(rec).shape[0] // xshape[0]
    cot = jax.random.normal(jax.random.PRNGKey(136),
                            (xshape[0], t_out, meta.g_dim)) / n
    old = {"a": _spd(137, meta.a_dim), "g": _spd(138, meta.g_dim)}
    out = {}
    for label, cfg in (("xla", CFG), ("pallas", CFG_PALLAS)):
        blk = B.resolve(meta)(meta, cfg)
        fn = jax.jit(lambda eps, b=blk: b.update_factors(
            old, rec, cot, {}, n, eps))
        out[label] = fn(jnp.float32(0.9))
    np.testing.assert_allclose(out["pallas"]["a"], out["xla"]["a"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out["pallas"]["g"], out["xla"]["g"],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("meta", [
    _conv_meta(c=16, k=2, bias=False),    # a_dim 32: kernel route
    _conv_meta(c=16, k=2, bias=True),     # a_dim 33: ragged fallback
], ids=["tiled", "ragged_bias"])
def test_conv_precondition_pallas_matches_xla(meta):
    a, g = _spd(140, meta.a_dim), _spd(141, meta.g_dim)
    v = jax.random.normal(jax.random.PRNGKey(142), (meta.a_dim, meta.g_dim))
    blk_x = B.resolve(meta)(meta, CFG)
    blk_p = B.resolve(meta)(meta, CFG_PALLAS)
    inv = blk_x.damped_inverse({"a": a, "g": g}, 0.3, method="eigh")
    want = blk_x.precondition(inv, v)
    got = jax.jit(blk_p.precondition)(inv, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # and the eigen-mode apply through rotate_rescale
    blk_xe = B.resolve(meta)(meta, CFG_EIGEN)
    blk_pe = B.resolve(meta)(meta, CFG_EIGEN.replace(kernel_backend="pallas"))
    eig = blk_xe.eigen_state({"a": a, "g": g}, 0.3)
    np.testing.assert_allclose(blk_pe.precondition_eigen(eig, v),
                               blk_xe.precondition_eigen(eig, v),
                               rtol=1e-4, atol=1e-4)


def test_kfac_rejects_unknown_inv_mode():
    from repro.core.kfac import KFAC
    from repro.models.mlp import MLP
    mlp = MLP([4, 4], loss="bernoulli")
    with pytest.raises(ValueError):
        KFAC(mlp, KFACConfig(inv_mode="spectral"))


# ---------------------------------------------------------------------------
# end-to-end: a KFAC step with kernel_backend="pallas" matches "xla"
# ---------------------------------------------------------------------------

def test_kfac_step_pallas_matches_xla():
    from repro.core.kfac import KFAC
    from repro.models.mlp import MLP

    dims = [8, 16, 8]
    mlp = MLP(dims, loss="bernoulli")
    params = mlp.init_params(jax.random.PRNGKey(0), sparse=False)
    x = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (64, dims[0])
                             ).astype(jnp.float32)
    batch = {"x": x, "y": x}
    rng = jax.random.PRNGKey(2)

    results = {}
    for backend in ("xla", "pallas"):
        cfg = KFACConfig(inv_mode="blkdiag", inverse_method="eigh", t1=0,
                         t2=0, kernel_backend=backend)
        opt = KFAC(mlp, cfg)
        state = opt.init(params, batch)
        state, grads, _ = jax.jit(opt.stats_grads)(state, params, batch, rng)
        state = jax.jit(opt.refresh_inverses)(state)
        new_params, state, _ = jax.jit(opt.apply_update)(
            state, params, grads, batch, rng)
        results[backend] = (new_params, state["factors"])

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5),
        results["pallas"], results["xla"])


def test_kfac_rejects_unknown_backend():
    from repro.core.kfac import KFAC
    from repro.models.mlp import MLP
    mlp = MLP([4, 4], loss="bernoulli")
    with pytest.raises(ValueError):
        KFAC(mlp, KFACConfig(kernel_backend="cuda"))
