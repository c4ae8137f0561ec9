"""Tile-size autotuner for the Pallas kernels (ROADMAP: "make the Pallas
kernels actually win").

Every kernel in this package is parameterized by block shapes (``bm/bn/bk``
for the matmul family, ``bt`` for the patch-factor kernel).  The right tile
depends on the backend, the problem shape and the dtype — a 512-wide factor
update wants different blocking on a TPU MXU than the 128-default that keeps
the interpreter tests fast.  This module:

  * enumerates the **legal** candidate tile configs per ``(kernel, shape)``
    (divisibility + MXU lane/sublane alignment — exactly the constraints the
    kernels assert),
  * times each candidate **on the live backend** with representative random
    inputs (compile excluded, median of a few calls),
  * memoizes the winner in a persistent on-disk JSON cache keyed on
    ``(kernel, shape, dtype, backend)`` so a shape is tuned once per machine,
  * and returns ``None`` whenever no candidate is legal or tuning is off —
    the caller keeps its existing einsum/XLA fallback, so the knob can never
    turn a working path into a crash.

Modes (``KFACConfig.autotune``, overridable via ``REPRO_AUTOTUNE``):

  ``off``    never tune; kernels run with their built-in default blocks.
             Bitwise-identical to the pre-autotuner behavior.
  ``cache``  consult the cache; tune on miss and persist the winner.
  ``force``  re-time every candidate and overwrite the cache entry (use
             after a driver/layout change invalidates old timings).

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.  A corrupted, unreadable or
schema-mismatched cache file is treated as empty (re-tune, then rewrite) —
it never raises.  Tuning happens at **trace time** (shapes are static), so
the tuned blocks are ordinary python ints by the time the kernel lowers.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

SCHEMA = 1
MODES = ("off", "cache", "force")
DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                             "autotune.json")

# in-process memo: cache_key -> config dict | None (None = "no legal
# candidate", also memoized so we don't re-enumerate every trace)
_MEMO: Dict[str, Optional[dict]] = {}


def resolve_mode(mode: str) -> str:
    """Config mode, overridden by the REPRO_AUTOTUNE env var when set."""
    env = os.environ.get("REPRO_AUTOTUNE", "").strip().lower()
    out = env if env in MODES else mode
    return out if out in MODES else "off"


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE)


def backend_tag(interpret: bool) -> str:
    """The cache's backend discriminator: a tuned tile is only valid for the
    platform (and execution mode) it was timed on."""
    b = jax.default_backend()
    return f"{b}_interp" if interpret and b != "tpu" else b


def cache_key(kernel: str, shape, dtype, backend: str) -> str:
    sh = "x".join(str(int(d)) for d in shape)
    return f"{kernel}|{sh}|{jnp.dtype(dtype).name}|{backend}"


# ---------------------------------------------------------------------------
# persistent cache (never raises: corruption -> empty)
# ---------------------------------------------------------------------------

def load_cache(path: Optional[str] = None) -> Dict[str, dict]:
    path = path or cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) or data.get("schema") != SCHEMA:
            return {}
        entries = data.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def save_entry(key: str, entry: dict, path: Optional[str] = None) -> None:
    path = path or cache_path()
    entries = load_cache(path)
    entries[key] = entry
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA, "entries": entries}, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass                     # a read-only FS must not break the step


def cached_entry(kernel: str, shape, dtype, *, interpret: bool,
                 path: Optional[str] = None) -> Optional[dict]:
    """The persisted winner for this problem, or None (no provenance)."""
    key = cache_key(kernel, shape, dtype, backend_tag(interpret))
    return load_cache(path).get(key)


def clear_memo() -> None:
    _MEMO.clear()


# ---------------------------------------------------------------------------
# candidate enumeration (mirrors each kernel's own legality asserts)
# ---------------------------------------------------------------------------

def _dim_blocks(dim: int, caps=(128, 256, 512)) -> List[int]:
    """Legal block sizes for one dim: whole 128-multiples that divide it, or
    the dim itself when it is a sub-128 MXU-lane-aligned size."""
    out = [b for b in caps if b <= dim and dim % b == 0]
    if not out and 0 < dim <= 128 and dim % 8 == 0:
        out = [dim]
    return out


def candidates(kernel: str, shape) -> List[dict]:
    """Candidate tile configs for ``kernel`` on problem ``shape``.

    Shape conventions (what the callers pass):
      factor_update   (n, d)         — x: (N, d), factor: (d, d)
      matmul          (m, k, n)
      precond         (d_in, d_out)  — both two-sided matmuls share a block
      rotate_rescale  (d_in, d_out)
      update_chain    (d_in, d_out)
      patch_factor    (t_out, c, taps, stride)
      flash_decode_paged (b, hq, hkv, hd, max_blocks, page_size)
    """
    if kernel == "factor_update":
        n, d = shape
        return [{"bm": bm, "bn": bn, "bk": bk}
                for bm in _dim_blocks(d) for bn in _dim_blocks(d)
                for bk in _dim_blocks(n)]
    if kernel == "matmul":
        m, k, n = shape
        return [{"bm": bm, "bn": bn, "bk": bk}
                for bm in _dim_blocks(m) for bn in _dim_blocks(n)
                for bk in _dim_blocks(k)]
    if kernel in ("precond", "rotate_rescale", "update_chain"):
        d_in, d_out = shape
        both = [b for b in (128, 256, 512)
                if d_in % b == 0 and d_out % b == 0]
        if not both:
            small = set(_dim_blocks(d_in)) & set(_dim_blocks(d_out))
            both = sorted(small)
        return [{"block": b} for b in both]
    if kernel == "patch_factor":
        t_out, c, taps, stride = shape
        return [{"bt": bt} for bt in (64, 128, 256, 512)
                if bt <= t_out and t_out % bt == 0 and taps <= bt * stride]
    if kernel == "flash_decode_paged":
        # KV-head block: each grid step streams one page slice for hb KV
        # heads (each with its whole query group), so hb > 1 trades fewer
        # grid steps for larger DMAs.  Legal hb divide the KV head count.
        b, hq, hkv, hd, nb, page = shape
        if hd % 8 != 0 or hkv == 0 or hq % hkv != 0:
            return []
        return [{"hb": hb} for hb in (1, 2, 3, 4, 8, 16)
                if hb <= hkv and hkv % hb == 0]
    return []


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

def default_timer(fn: Callable[[], jax.Array], iters: int = 3) -> float:
    """Median-free mean wall-clock per call in µs, compile excluded."""
    jax.block_until_ready(fn())          # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _bench_inputs(key, shapes, dtypes):
    ks = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    return [jax.random.normal(k, s).astype(dt)
            for k, s, dt in zip(ks, shapes, dtypes)]


def _make_runner(kernel: str, shape, dtype, interpret: bool,
                 cfg: dict) -> Callable[[], jax.Array]:
    """A zero-arg jitted call of ``kernel`` at ``cfg`` on representative
    random inputs (held alive in the closure)."""
    if kernel == "factor_update":
        from repro.kernels.factor_update import factor_update
        n, d = shape
        x, c = _bench_inputs(0, [(n, d), (d, d)], [dtype, jnp.float32])
        f = jax.jit(lambda x, c: factor_update(
            x, c, alpha=0.05, beta=0.95, interpret=interpret, **cfg))
        return lambda: f(x, c)
    if kernel == "matmul":
        from repro.kernels.matmul import matmul
        m, k, n = shape
        a, b = _bench_inputs(1, [(m, k), (k, n)], [dtype, dtype])
        f = jax.jit(lambda a, b: matmul(a, b, interpret=interpret, **cfg))
        return lambda: f(a, b)
    if kernel == "precond":
        from repro.kernels.precond import precondition
        d_in, d_out = shape
        a, v, g = _bench_inputs(2, [(d_in, d_in), (d_in, d_out),
                                    (d_out, d_out)], [jnp.float32] * 3)
        f = jax.jit(lambda a, v, g: precondition(
            a, v, g, interpret=interpret, **cfg))
        return lambda: f(a, v, g)
    if kernel == "rotate_rescale":
        from repro.kernels.rotate_rescale import rotate_rescale
        d_in, d_out = shape
        qa, v, qg, s = _bench_inputs(
            3, [(d_in, d_in), (d_in, d_out), (d_out, d_out),
                (d_in, d_out)], [jnp.float32] * 4)
        f = jax.jit(lambda qa, v, qg, s: rotate_rescale(
            qa, v, qg, s, lam=1e-6, interpret=interpret, **cfg))
        return lambda: f(qa, v, qg, s)
    if kernel == "update_chain":
        from repro.kernels.update_chain import precond_momentum
        d_in, d_out = shape
        a, v, g, m = _bench_inputs(
            4, [(d_in, d_in), (d_in, d_out), (d_out, d_out),
                (d_in, d_out)], [jnp.float32] * 4)
        f = jax.jit(lambda a, v, g, m: precond_momentum(
            a, v, g, m, alpha=-0.05, mu=0.9, interpret=interpret,
            **cfg)[0])
        return lambda: f(a, v, g, m)
    if kernel == "patch_factor":
        from repro.kernels.patch_factor import patch_factor
        t_out, c, taps, stride = shape
        d = taps * c
        x, old = _bench_inputs(5, [(2, t_out * stride + taps, c), (d, d)],
                               [dtype, jnp.float32])
        f = jax.jit(lambda x, old: patch_factor(
            x, old, taps=taps, stride=stride, t_out=t_out, alpha=0.05,
            beta=0.95, interpret=interpret, **cfg))
        return lambda: f(x, old)
    if kernel == "flash_decode_paged":
        from repro.kernels.flash_decode import flash_decode_paged
        b, hq, hkv, hd, nb, page = shape
        num_pages = 1 + b * nb
        q, kp, vp = _bench_inputs(
            6, [(b, hq, hd), (num_pages, hkv, page, hd),
                (num_pages, hkv, page, hd)], [dtype] * 3)
        rs = jax.random.split(jax.random.PRNGKey(7), 1)[0]
        pt = jax.random.permutation(
            rs, jnp.arange(1, num_pages, dtype=jnp.int32)
        )[:b * nb].reshape(b, nb)
        lens = jnp.full((b,), nb * page, jnp.int32)
        f = jax.jit(lambda q, kp, vp, lens, pt: flash_decode_paged(
            q, kp, vp, lens, pt, interpret=interpret, **cfg))
        return lambda: f(q, kp, vp, lens, pt)
    raise KeyError(f"no autotune runner for kernel {kernel!r}")


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

def tuned(kernel: str, shape, dtype, *, interpret: bool, mode: str = "off",
          timer: Optional[Callable] = None,
          path: Optional[str] = None) -> Optional[dict]:
    """The winning tile config (kwargs for the kernel) or ``None``.

    ``None`` means: tuning is off, no candidate is legal, or every candidate
    failed to run — the caller proceeds exactly as before (default blocks or
    its einsum fallback).  Tuning happens eagerly (shapes are static python
    ints), so this is safe to call at trace time; results are memoized
    in-process and persisted on disk.
    """
    mode = resolve_mode(mode)
    if mode == "off":
        return None
    shape = tuple(int(d) for d in shape)
    key = cache_key(kernel, shape, dtype, backend_tag(interpret))
    if mode != "force" and key in _MEMO:
        return _MEMO[key]
    if mode != "force":
        entry = load_cache(path).get(key)
        if entry is not None and isinstance(entry.get("cfg"), (dict,
                                                               type(None))):
            cfg = entry["cfg"]
            cands = candidates(kernel, shape)
            # stale guard: a cached winner that is no longer a legal
            # candidate (kernel constraints changed) forces a re-tune
            if cfg is None or cfg in cands:
                _MEMO[key] = cfg
                return cfg
    cfg = _tune(kernel, shape, dtype, interpret, timer or default_timer,
                key, path)
    _MEMO[key] = cfg
    return cfg


def _tune(kernel, shape, dtype, interpret, timer, key, path):
    cands = candidates(kernel, shape)
    best, best_us = None, float("inf")
    timings = {}
    for cfg in cands:
        try:
            us = float(timer(_make_runner(kernel, shape, dtype, interpret,
                                          cfg)))
        except Exception:        # noqa: BLE001 — an illegal lowering is a
            continue             # declined candidate, never a crash
        timings[json.dumps(cfg, sort_keys=True)] = us
        if us < best_us:
            best, best_us = cfg, us
    save_entry(key, {"cfg": best,
                     "us": None if best is None else best_us,
                     "timings": timings}, path)
    return best
