"""Flash-decode kernel: one query token per row against a long KV cache.

The serve-side counterpart of the Perf-1 cache layout (EXPERIMENTS §Perf):
the key axis is the grid's innermost dimension, so on a sequence-sharded
cache each core streams only its KV slice; the online-softmax scratch
carries (m, l, acc) across key blocks.  The cache's valid lengths arrive as
a ``(B,)`` scalar-prefetch vector — every batch row masks its own
``[0, len_b)`` prefix (continuous batching: slots decode at *different*
positions), with an optional sliding window (``[len_b - window, len_b)``)
and attention-score softcap so the gemma2-style local layers stay on the
kernel path.

Each grid step serves one KV head's whole GQA group: q is viewed as
``(B, Hkv, G, hd)`` so its block spans the last two dims whole, as the TPU
lowering requires, and each streamed K/V block is read once for all ``G``
query heads that share it.

``interpret`` has no hardcoded default: ``None`` resolves from the live
backend (compiled on TPU, interpreter elsewhere), so a direct caller can
never silently run the interpreter on a compiled backend.

``flash_decode_paged`` is the block-indexed paged-attention variant
(PagedAttention/vLLM shape): K/V live in a physical page pool
``(num_pages, hkv, page_size, hd)`` shared by every slot, and each row's
``(max_blocks,)`` page-table row rides in as a *second* scalar-prefetch
operand.  The grid's innermost dimension walks the row's logical pages and
the K/V BlockSpec index maps read the page table to DMA each physical page
in place — no dense ``(B, S_view)`` gather view is ever materialized.  The
pool keeps ``(page_size, hd)`` as its last two dims, so a page block is
whole in both, which the TPU lowering requires.
Per-row valid lengths, the sliding window and the softcap behave exactly as
in the dense kernel, so the two are differentially testable against the
same einsum oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30
# f32 operands multiply in f32 (not one bf16 pass): the kernel is checked
# against the f32 einsum oracle, and decode is bound by the page DMAs
_F32 = jax.lax.Precision.HIGHEST


def _softmax_step(q, k, v, k_pos, length, m_ref, l_ref, acc_ref, *, scale,
                  window, cap):
    """One online-softmax step of a ``(G, hd)`` query group against a
    ``(n, hd)`` key/value block whose positions are ``k_pos`` (G, n)."""
    s = jax.lax.dot_general(q, k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())), precision=_F32,
                            preferred_element_type=jnp.float32) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    valid = k_pos < length                       # beyond-length entries are
    if window:                                   # null/stale: masked out
        valid &= k_pos >= length - window
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jnp.dot(p, v.astype(jnp.float32), precision=_F32,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finish(acc, l):
    return acc / jnp.maximum(l, 1e-30)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, k_steps, bk, window, cap):
    bb = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], bk), 1)
    _softmax_step(q, k_ref[0, 0], v_ref[0, 0], k_pos, len_ref[bb],
                  m_ref, l_ref, acc_ref, scale=scale, window=window, cap=cap)

    @pl.when(ik == k_steps - 1)
    def _done():
        o_ref[0, 0] = _finish(acc_ref[...], l_ref[...]).astype(o_ref.dtype)


def flash_decode(q, k, v, lengths, *, bk: int = 128, window: int = 0,
                 cap: float = 0.0, interpret=None):
    """q: (B, Hq, hd) one token per row; k, v: (B, Hkv, S, hd); lengths:
    ``(B,)`` int32 valid-cache-entry counts (a scalar broadcasts — the
    legacy single-length form).  Returns (B, Hq, hd).

    window > 0 restricts row b to keys in ``[lengths[b]-window,
    lengths[b])``; cap > 0 applies the pre-softmax score softcap.
    ``interpret=None`` resolves from the backend (never silently the
    interpreter on TPU)."""
    b, hq, hd = q.shape
    _, hkv, s_len, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bk = min(bk, s_len)
    assert s_len % bk == 0
    k_steps = s_len // bk
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(hd),
                               k_steps=k_steps, bk=bk, window=int(window),
                               cap=float(cap))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, k_steps),
            in_specs=[
                pl.BlockSpec((1, 1, group, hd),
                             lambda bb, h, ik, lens: (bb, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda bb, h, ik, lens: (bb, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda bb, h, ik, lens: (bb, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, hd),
                                   lambda bb, h, ik, lens: (bb, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths, q.reshape(b, hkv, group, hd), k, v)
    return out.reshape(b, hq, hd)


# ---------------------------------------------------------------------------
# block-indexed paged attention
# ---------------------------------------------------------------------------

def _paged_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, n_blocks, page, window,
                  cap):
    bb = pl.program_id(0)
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    hb, group = q_ref.shape[1], q_ref.shape[2]
    k_pos = ib * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
    length = len_ref[bb]
    for h in range(hb):              # the KV heads of this block, unrolled
        _softmax_step(q_ref[0, h].astype(jnp.float32), k_ref[0, h],
                      v_ref[0, h], k_pos, length, m_ref.at[h], l_ref.at[h],
                      acc_ref.at[h], scale=scale, window=window, cap=cap)

    @pl.when(ib == n_blocks - 1)
    def _done():
        o_ref[0] = _finish(acc_ref[...], l_ref[...]).astype(o_ref.dtype)


def flash_decode_paged(q, k_pool, v_pool, lengths, page_table, *, hb: int = 1,
                       window: int = 0, cap: float = 0.0, interpret=None):
    """Paged decode: one query token per row against a shared page pool.

    q: (B, Hq, hd); k_pool, v_pool: (num_pages, Hkv, page_size, hd);
    lengths: (B,) int32 valid-entry counts; page_table: (B, max_blocks)
    int32 rows of physical page ids (unused tail entries must point at a
    masked page, e.g. the allocator's null page 0).  Returns (B, Hq, hd).

    Both the length vector and the page table ride in as scalar-prefetch
    operands: the grid's innermost dim walks each row's ``max_blocks``
    logical pages, and the K/V index maps look the physical page up in the
    table, so each step DMAs ``hb`` heads' ``(page_size, hd)`` slices of
    one page — no gathered dense view exists anywhere.  ``hb`` is the
    tunable KV-head block (it divides Hkv); each KV head's whole query
    group rides with it (autotuner coverage:
    ``candidates("flash_decode_paged", ...)``).
    """
    b, hq, hd = q.shape
    num_pages, hkv, page, _ = k_pool.shape
    assert hq % hkv == 0
    group = hq // hkv
    assert hkv % hb == 0, (hb, hkv)
    n_blocks = page_table.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    page_table = jnp.asarray(page_table, jnp.int32)
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd),
                               n_blocks=n_blocks, page=page,
                               window=int(window), cap=float(cap))
    kv_spec = pl.BlockSpec((1, hb, page, hd),
                           lambda bb, jh, ib, lens, pt: (pt[bb, ib], jh, 0, 0))
    q_spec = pl.BlockSpec((1, hb, group, hd),
                          lambda bb, jh, ib, lens, pt: (bb, jh, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv // hb, n_blocks),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hb, group, 1), jnp.float32),
                pltpu.VMEM((hb, group, 1), jnp.float32),
                pltpu.VMEM((hb, group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths, page_table, q.reshape(b, hkv, group, hd), k_pool, v_pool)
    return out.reshape(b, hq, hd)
