"""Paged KV cache: block pools per layer group + gather/scatter views.

Layout
------
For every attention pattern position ``posX`` of the model there is one
``k`` and one ``v`` pool of shape ``(ng, num_pages, hkv, page_size, hd)``
(``ng`` = the model's scan-group leading dim; same dtype as the serve-side
dense cache, bfloat16).  ``(page_size, hd)`` are the last two dims, so the
paged kernel's page block spans them whole, as the TPU lowering requires.  All layers share one *page-id space*: a slot's
page table row lists the physical pages backing its logical positions in
order, and that same row indexes every layer's pools — exactly the
vLLM-style block table, minus per-layer tables.

The default decode route is *block-indexed*: ``model.decode_step`` takes
the pools plus the ``(B, max_blocks)`` page table straight through to
``ops.flash_decode_paged`` — each layer scatters its one new KV row into
the slot's physical page and attends the pool in place (page table as a
scalar-prefetch operand of the Pallas kernel), so no dense per-row view is
ever materialized on the hot path.  ``gather`` + ``scatter_token`` remain
as the *oracle route* (``Engine(decode_route="gather")``): pages gathered
back into the ``(ng, B, S_view, hkv, hd)`` dense cache (``S_view =
max_blocks * page_size``, fixed so the step compiles once), decode against
it, one-token scatter back — the einsum/XLA reference the paged route is
differentially tested against.  Rows whose slot is idle carry a page table
of null pages (page 0, reserved by the allocator), so their writes never
touch a live allocation on either route.

Attention never reads stale bytes from a *reused* page: row ``b`` of the
gathered view is masked to ``[0, len_b)`` by the per-slot length vector
(``ops.flash_decode``), and every position in that prefix was written by
the current owner (prefill covers ``[0, prompt_len)``, decode extends one
position per step) — a recycled page is therefore fully overwritten before
any of it is attended.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _check_supported(model) -> None:
    cfg = model.cfg
    bad = [s.attn for s in model.pattern if s.attn not in ("global", "local")]
    if bad or cfg.encoder_layers or any(s.cross for s in model.pattern):
        raise NotImplementedError(
            f"paged serving engine supports attention-only decoders; "
            f"{cfg.name} has attn kinds "
            f"{sorted({s.attn for s in model.pattern})}"
            + (", encoder/cross-attention" if cfg.encoder_layers else ""))


class PagedKVCache:
    """Owns the pool layout + the pure gather/scatter functions used inside
    the engine's jitted step.  The pools themselves are a plain pytree held
    by the engine (functional updates)."""

    def __init__(self, model, *, batch_slots: int, max_len: int,
                 page_size: int = 8, num_pages: int = None,
                 dtype=jnp.bfloat16):
        _check_supported(model)
        if page_size < 1:
            raise ValueError(f"page_size={page_size}")
        self.model = model
        self.b = batch_slots
        self.max_len = max_len
        self.page_size = page_size
        self.max_blocks = max(1, math.ceil(max_len / page_size))
        self.s_view = self.max_blocks * page_size
        # default capacity: every slot can reach max_len, + 1 null page
        self.num_pages = (1 + batch_slots * self.max_blocks
                          if num_pages is None else num_pages)
        self.dtype = dtype
        cfg = model.cfg
        self.layer_names = [f"pos{i}" for i in range(len(model.pattern))]
        self._kv_shape = (model.n_groups, self.num_pages, cfg.n_kv_heads,
                          page_size, cfg.hd)

    def blocks_for(self, n_positions: int) -> int:
        """Pages needed to back ``n_positions`` logical cache entries."""
        return max(1, math.ceil(n_positions / self.page_size))

    # -- pool construction -------------------------------------------------
    def init_pools(self) -> Dict[str, Dict[str, jax.Array]]:
        """Zeroed pools (structurally — a fresh slot attends nothing but
        positions it wrote, and the null page is all-zero garbage)."""
        return {name: {"k": jnp.zeros(self._kv_shape, self.dtype),
                       "v": jnp.zeros(self._kv_shape, self.dtype)}
                for name in self.layer_names}

    # -- pure views (jit-safe) ---------------------------------------------
    def gather(self, pools, page_table):
        """pools + ``(B, max_blocks)`` page table -> dense decode cache
        ``{posX: {k,v: (ng, B, S_view, hkv, hd)}}`` in logical order."""
        ng = self.model.n_groups

        def one(pool):
            g = jnp.take(pool, page_table, axis=1)  # (ng,B,nb,hkv,P,hd)
            g = g.transpose(0, 1, 2, 4, 3, 5)       # (ng,B,nb,P,hkv,hd)
            return g.reshape(ng, self.b, self.s_view, pool.shape[2],
                             pool.shape[4])

        return {name: {"k": one(p["k"]), "v": one(p["v"])}
                for name, p in pools.items()}

    def scatter_token(self, pools, dense_cache, page_table, pos):
        """Write each row's KV at logical position ``pos[b]`` (just spliced
        into the dense view by ``decode_step``) back to its physical page."""
        bidx = jnp.arange(self.b)
        page = jnp.take_along_axis(page_table,
                                   (pos // self.page_size)[:, None],
                                   axis=1)[:, 0]
        off = pos % self.page_size
        out = {}
        for name, p in pools.items():
            # (B, ng, hkv, hd): the indexed dims lead, as ``.at`` orders them
            row_k = jnp.swapaxes(dense_cache[name]["k"][:, bidx, pos], 0, 1)
            row_v = jnp.swapaxes(dense_cache[name]["v"][:, bidx, pos], 0, 1)
            out[name] = {
                "k": p["k"].at[:, page, :, off].set(
                    row_k.astype(p["k"].dtype)),
                "v": p["v"].at[:, page, :, off].set(
                    row_v.astype(p["v"].dtype)),
            }
        return out

    # -- host-side prefill write ------------------------------------------
    def write_prefill(self, pools, pages, prefill_cache, prompt_len: int,
                      row: int = 0):
        """Write row ``row`` of a (possibly multi-request) prefill cache
        (``(ng, B, Tp, hkv, hd)`` leaves) into the first
        ``blocks_for(prompt_len)`` of ``pages``.  Batched admission prefills
        several same-length requests in one forward and peels each row into
        its own slot's pages through this."""
        nb = self.blocks_for(prompt_len)
        if nb > len(pages):
            raise ValueError(f"prompt needs {nb} pages, slot holds "
                             f"{len(pages)}")
        pids = jnp.asarray(pages[:nb], jnp.int32)
        pad = nb * self.page_size - prompt_len
        ng = self.model.n_groups
        out = {}
        for name in self.layer_names:
            src = prefill_cache[name]
            new = {}
            for kv in ("k", "v"):
                x = src[kv][:, row:row + 1, :prompt_len]
                x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                x = x.reshape(ng, nb, self.page_size, *x.shape[3:])
                new[kv] = pools[name][kv].at[:, pids].set(
                    jnp.swapaxes(x, 2, 3).astype(self.dtype))
            out[name] = new
        return out
