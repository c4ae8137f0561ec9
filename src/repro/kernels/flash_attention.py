"""Flash attention forward kernel (GQA + causal + sliding window + softcap).

VMEM-tiled online-softmax attention for the serving path of the dense
transformer archs (yi/llama/gemma/phi; gemma2's score softcap and local
windows included).  Grid: (B, Hq, Tq/bq, Tk/bk) with the key axis innermost;
running max/sum and the output accumulator live in VMEM scratch.

The training path keeps the chunked pure-jnp attention (repro.models.layers)
— which doubles as this kernel's oracle in the interpret-mode test sweeps.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, cap, k_steps, bq, bk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jnp.dot(p, v_ref[0, 0].astype(jnp.float32),
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ik == k_steps - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    cap: float = 0.0, bq: int = 128, bk: int = 128,
                    interpret=None):
    """q: (B, Hq, Tq, hd);  k, v: (B, Hkv, Tk, hd).  Returns (B, Hq, Tq, hd)."""
    b, hq, tq, hd = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq, bk = min(bq, tq), min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0
    k_steps = tk // bk
    grid = (b, hq, tq // bq, k_steps)
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        cap=cap, k_steps=k_steps, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, h, iq, ik, g=group: (bb, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, h, iq, ik, g=group: (bb, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
