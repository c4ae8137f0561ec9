"""Dispatch layer over the decode kernels, with the einsum oracle beside.

On a TPU the Pallas kernel runs, compiled, wherever its shape applies;
elsewhere the masked einsum oracle runs.  Both are decided when a call is
traced, never when this module is imported.  ``use_pallas(on, interpret)``
overrides the choice — the CPU tests use it to drive the kernels in
interpret mode — and ``use_pallas(None)`` restores the backend default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import flash_decode as _fd
from repro.kernels.backend import on_tpu, resolve_interpret

_STATE = {"use_pallas": None, "interpret": None}


def use_pallas(on=True, interpret=None):
    """Force the kernel route on (or off); ``None`` = the backend default."""
    _STATE["use_pallas"] = on
    _STATE["interpret"] = interpret


def enabled() -> bool:
    on = _STATE["use_pallas"]
    return on_tpu() if on is None else on


def flash_decode_ref(q, k, v, lengths, *, window=0, cap=0.0):
    """The masked-einsum decode oracle: (B,Hq,hd) x (B,Hkv,S,hd) with
    per-row ``[0, len_b)`` (optionally windowed, softcapped) masking.  The
    XLA fallback of ``flash_decode`` *and* the differential reference both
    the dense and the paged Pallas kernels are tested against."""
    b, hq, hd = q.shape
    hkv, s_len = k.shape[1], k.shape[2]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    sc = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32))
    sc = sc / jnp.sqrt(jnp.float32(hd))
    if cap:
        sc = cap * jnp.tanh(sc / cap)
    k_pos = jnp.arange(s_len)
    valid = k_pos[None, :] < lengths[:, None]            # (B, S) per-row mask
    if window:
        valid &= k_pos[None, :] >= lengths[:, None] - window
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, hd).astype(q.dtype)


def flash_decode(q, k, v, lengths, *, bk=128, window=0, cap=0.0):
    """One-token decode vs a long cache: (B,Hq,hd) x (B,Hkv,S,hd).

    ``lengths`` is a ``(B,)`` int32 vector of per-row valid cache entries
    (a scalar broadcasts): continuous-batching slots decode at different
    positions, so each row masks its own ``[0, len_b)`` prefix —
    ``[len_b - window, len_b)`` when ``window`` > 0 (gemma2 local layers);
    ``cap`` > 0 soft-caps the attention scores."""
    b = q.shape[0]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    if enabled() and k.shape[2] % bk == 0 and q.shape[-1] % 8 == 0:
        return _fd.flash_decode(q, k, v, lengths, bk=bk, window=window,
                                cap=cap, interpret=_STATE["interpret"])
    return flash_decode_ref(q, k, v, lengths, window=window, cap=cap)


def paged_gather(k_pool, v_pool, page_table):
    """Materialize the dense ``(B, Hkv, S_view, hd)`` gather view of a page
    pool ``(num_pages, Hkv, page, hd)`` — the serving engine's *oracle*
    decode route (and the paged kernel's differential reference)."""
    nb = page_table.shape[1]
    num_pages, hkv, page, hd = k_pool.shape
    b = page_table.shape[0]

    def one(pool):
        g = jnp.take(pool, page_table, axis=0)       # (B, nb, hkv, P, hd)
        return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nb * page, hd)

    return one(k_pool), one(v_pool)


def flash_decode_paged(q, k_pool, v_pool, lengths, page_table, *, window=0,
                       cap=0.0, tune_mode: str = "off"):
    """Block-indexed paged decode: (B,Hq,hd) against a shared page pool
    ``(num_pages, Hkv, page_size, hd)`` through each row's
    ``(max_blocks,)`` page-table row.  The Pallas route walks the pages in
    place (page table as a scalar-prefetch operand — no dense gather
    view); the XLA fallback gathers the view and runs the einsum oracle, so
    fallback == oracle by construction.  ``tune_mode`` threads the
    autotuner (``REPRO_AUTOTUNE`` env overrides) for the KV-head block
    ``hb``."""
    b, hq, hd = q.shape
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    if enabled() and hd % 8 == 0:
        interpret = resolve_interpret(_STATE["interpret"])
        hkv, page = k_pool.shape[1], k_pool.shape[2]
        cfg = _at.tuned("flash_decode_paged",
                        (b, hq, hkv, hd, page_table.shape[1], page),
                        q.dtype, interpret=interpret, mode=tune_mode) or {}
        return _fd.flash_decode_paged(q, k_pool, v_pool, lengths, page_table,
                                      window=window, cap=cap,
                                      interpret=interpret, **cfg)
    kd, vd = paged_gather(k_pool, v_pool, page_table)
    return flash_decode_ref(q, kd, vd, lengths, window=window, cap=cap)
