"""Smoke run of the main paths on a TPU: K-FAC training, kernel parity and
paged serving of smollm-135m, through the entry points a user calls.

    python3 chip_smoke.py              # one chip: train, parity, serve
    python3 chip_smoke.py --chips 4    # four chips: sharded vs serial refresh

Each phase prints its own ``[phase]`` lines and checks its own results; the
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero and prints no result.  Times printed here are smoke figures taken
on one run, not benchmark numbers.

Phases (one chip):
  train   K-FAC (launcher defaults: blkdiag, Newton-Schulz inverses, T3=5)
          on smollm-135m at its published widths, 8 steps of 4 x 2048
          tokens.  Every loss finite, no rejected step, last loss below
          the first.
  parity  The reduced smollm (widths that tile) for 3 K-FAC steps with the
          Pallas kernels on the TPU against the einsum path on the host CPU,
          for inv_mode blkdiag and eigen; loss histories within PARITY_RTOL.
  serve   8 greedy requests of a few hundred prompt tokens, 32 new tokens
          each, through the serving Engine on the paged route and on the
          gather (einsum oracle) route.  The compiled paged-attention kernel
          is in the decode program and agrees with the oracle within
          ATTN_ATOL on the served prompts' KV pages.

``--chips 4`` runs only the multi-chip path: K-FAC on a (4, 1) data mesh
with refresh_mode="sharded" against "serial"; params and inverses after
the warm-up refreshes must be bitwise equal (docs/distributed.md).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.blocks import route_counts  # noqa: E402
from repro.obs import CompileClock  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "smollm-135m"
# loss agreement between the compiled kernels on the TPU and the einsum path
# on the host CPU, both at full f32 matmul precision: ten times below what
# one K-FAC step moves the loss here, well above f32 rounding noise
PARITY_RTOL = 1e-4
# paged-attention kernel vs the gather+einsum oracle on bf16 pages, f32 math
ATTN_ATOL = 1e-4


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def custom_calls(compiled) -> int:
    """Mosaic kernels in a compiled program (0 = no Pallas kernel ran)."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _launch(argv):
    from repro.launch import train as launch_train
    return launch_train.build(launch_train.parse_args(argv))


def _fit(run, steps, jsonl=None):
    """Trainer.fit through the launcher's objects; returns (losses,
    per-step seconds or None, rejected-step count, result)."""
    out = run.trainer.fit(run.params, run.data, steps, log=lambda *_: None)
    losses = [h["loss"] for h in out["history"]]
    walls = None
    if jsonl is not None:
        run.obs.close()
        with open(jsonl) as f:
            events = [json.loads(line) for line in f]
        walls = [e["wall_s"] for e in events if e.get("event") == "train_step"]
    rejected = int(run.trainer.obs.counter("train/rejected_steps").value)
    return losses, walls, rejected, out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _routes(counts) -> str:
    return " ".join(f"{op}=pallas:{c['pallas']}/einsum:{c['einsum']}"
                    for op, c in counts.items())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(arch=ARCH, reduced=False, steps=8, batch=4, seq=2048):
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, "train_events.jsonl")
        argv = ["--arch", arch, "--steps", str(steps), "--global_batch",
                str(batch), "--seq", str(seq), "--obs_jsonl", jsonl]
        run = _launch(argv + (["--reduced"] if reduced else []))
        eng = run.opt.engine
        say("train", f"arch={run.cfg.name} params={run.lm.n_params():,} "
                     f"tokens/step={batch * seq} "
                     f"inv_mode={eng.cfg.inv_mode} "
                     f"kernel_backend={eng.cfg.kernel_backend}")
        with CompileClock() as clock:
            losses, walls, rejected, out = _fit(run, steps, jsonl)
    say("train", "losses " + " ".join(f"{x:.4f}" for x in losses))
    say("train", "step_s " + " ".join(f"{w:.3f}" for w in walls))
    say("train", f"compile_s={clock.seconds:.1f} steady_step_s="
                 f"{statistics.median(walls[-2:]):.3f} (smoke figure, "
                 f"median of the last 2 steps; not a benchmark)")
    stats = jax.devices()[0].memory_stats() or {}
    say("train", f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    say("train", f"kernel routes: {_routes(route_counts(eng.blocks))}")
    state, params = out["state"], out["params"]
    batch0 = run.data.batch(0)
    rng = jax.random.PRNGKey(0)
    n_stats = custom_calls(jax.jit(eng.stats_grads).lower(
        state, params, batch0, rng).compile())
    say("train", f"tpu_custom_calls: stats_grads={n_stats}")
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    _check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    _check(rejected == 0, f"{rejected} rejected steps")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    say("train", f"ok: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
                 f"0 rejected steps")


def parity_phase(steps=3, modes=("blkdiag", "eigen")):
    kernel_dev = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    for mode in modes:
        argv = ["--arch", ARCH, "--reduced", "--steps", str(steps),
                "--global_batch", "8", "--seq", "64", "--inv_mode", mode]
        with jax.default_matmul_precision("highest"):
            with jax.default_device(kernel_dev):
                run_k = _launch(argv + ["--kernel_backend", "pallas"])
                got, _, rej_k, _ = _fit(run_k, steps)
            with jax.default_device(cpu):
                run_x = _launch(argv + ["--kernel_backend", "xla"])
                want, _, rej_x, _ = _fit(run_x, steps)
        rel = max(abs(g - w) / abs(w) for g, w in zip(got, want))
        rc = route_counts(run_k.opt.engine.blocks)
        say("parity", f"{mode}: pallas[{kernel_dev.platform}] "
                      + " ".join(f"{x:.6f}" for x in got)
                      + " | xla[cpu] " + " ".join(f"{x:.6f}" for x in want)
                      + f" | max rel diff {rel:.3e} (rtol {PARITY_RTOL})")
        say("parity", f"{mode}: kernel routes: {_routes(rc)}")
        applied = "rotate_rescale" if mode == "eigen" else "precond"
        _check(rc.get(applied, {}).get("pallas", 0) > 0,
               f"{mode}: no block took the {applied} kernel")
        _check(rc.get("factor_update.g", {}).get("pallas", 0) > 0,
               f"{mode}: no block took the factor_update kernel")
        _check(rej_k == 0 and rej_x == 0, f"{mode}: rejected steps")
        _check(bool(np.isfinite(got).all()), f"{mode}: non-finite {got}")
        _check(rel <= PARITY_RTOL, f"{mode}: loss histories differ by {rel}")
    say("parity", "ok")


def _prompts(vocab, n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=lengths[i % len(lengths)]).tolist()
            for i in range(n)]


def serve_phase(arch=ARCH, reduced=False, n_req=8, lengths=(256, 320),
                max_new=32, page_size=8):
    from repro.configs import get_config, get_reduced_config
    from repro.kernels import ops
    from repro.models.lm import LM
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Request

    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    lm = LM(cfg)
    params = lm.init_params(jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab_size, n_req, lengths)
    max_len = max(lengths) + max_new
    say("serve", f"arch={cfg.name} requests={n_req} prompt_lens="
                 f"{sorted(set(len(p) for p in prompts))} max_new={max_new} "
                 f"page_size={page_size}")
    outs = {}
    for route in ("paged", "gather"):
        eng = Engine(lm, params, batch_slots=n_req, max_len=max_len,
                     page_size=page_size, decode_route=route)
        reqs = [Request(uid=i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        rep = eng.run(reqs)
        wall = time.perf_counter() - t0
        _check(len(rep.completed) == n_req,
               f"{route}: {len(rep.completed)} of {n_req} completed")
        for r in reqs:
            _check(len(r.out) == max_new and
                   all(0 <= t < cfg.vocab_size for t in r.out),
                   f"{route}: request {r.uid} returned {r.out}")
        outs[route] = [r.out for r in reqs]
        say("serve", f"{route}: {rep.steps} engine steps, {n_req} "
                     f"completed in {wall:.2f}s (smoke figure, compile "
                     f"included)")
    agree = np.mean([a == b for pa, pg in zip(outs["paged"], outs["gather"])
                     for a, b in zip(pa, pg)])
    say("serve", f"greedy tokens equal on both routes: {agree:.4f}")

    # the decode program the paged route runs holds the compiled kernel
    kv = eng.kv
    pools = kv.init_pools()
    pt = np.zeros((n_req, kv.max_blocks), np.int32)
    nxt = 1
    toks = np.zeros((n_req, max(lengths)), np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p
    _, cache = jax.jit(lm.prefill)(params, {"tokens": jnp.asarray(toks)})
    for b, p in enumerate(prompts):
        nb = kv.blocks_for(len(p))
        pages = list(range(nxt, nxt + nb))
        pt[b, :nb] = pages
        nxt += nb
        pools = kv.write_prefill(pools, pages, cache, len(p), row=b)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    decode = jax.jit(lambda p, c, t, ps, tb: lm.decode_step(
        p, c, t, ps, page_table=tb))
    n_calls = custom_calls(decode.lower(
        params, pools, jnp.zeros((n_req, 1), jnp.int32), pos,
        jnp.asarray(pt)).compile())
    say("serve", f"tpu_custom_calls in the paged decode program: {n_calls}")
    _check(n_calls >= 1, "paged decode program has no Pallas kernel")

    # decode attention on the served prompts' pages: kernel vs oracle
    worst = 0.0
    lens = pos - jnp.arange(n_req, dtype=jnp.int32) * 3      # ragged rows
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (n_req, cfg.n_heads, cfg.hd), jnp.float32)
    for name in kv.layer_names:
        for layer in (0, lm.n_groups - 1):
            kp, vp = pools[name]["k"][layer], pools[name]["v"][layer]
            with jax.default_matmul_precision("highest"):
                got = ops.flash_decode_paged(q, kp, vp, lens, pt)
                kd, vd = ops.paged_gather(kp, vp, jnp.asarray(pt))
                want = ops.flash_decode_ref(q, kd, vd, lens)
            worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    say("serve", f"paged kernel vs gather oracle: max abs diff {worst:.3e} "
                 f"(atol {ATTN_ATOL}, f32 math on bf16 pages)")
    _check(ops.enabled(), "ops routes decode to the einsum oracle")
    _check(worst <= ATTN_ATOL, f"decode attention differs by {worst}")
    say("serve", "ok")


def sharded_phase(arch=ARCH, reduced=False, steps=3, batch=4, seq=2048):
    """refresh_mode sharded vs serial on one (n, 1) data mesh: params and
    inverses bitwise equal after the warm-up refreshes."""
    n = len(jax.devices())
    base = ["--arch", arch, "--steps", str(steps), "--global_batch",
            str(batch), "--seq", str(seq), "--mesh", "local",
            "--kernel_backend", "xla"] + (["--reduced"] if reduced else [])
    res = {}
    for mode in ("serial", "sharded"):
        run = _launch(base + ["--refresh_mode", mode])
        t0 = time.perf_counter()
        losses, _, rejected, out = _fit(run, steps)
        say("sharded", f"{mode}: mesh {dict(run.mesh.shape)} losses "
                       + " ".join(f"{x:.6f}" for x in losses)
                       + f" ({time.perf_counter() - t0:.1f}s, compile "
                         "included)")
        _check(rejected == 0 and bool(np.isfinite(losses).all()),
               f"{mode}: rejected or non-finite steps {losses}")
        res[mode] = out
    bad = []
    ser, shd = res["serial"], res["sharded"]
    for what, a, b in (("params", ser["params"], shd["params"]),
                       ("inv", ser["state"].inv, shd["state"].inv)):
        for (kp, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                              jax.tree_util.tree_leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            if not np.array_equal(x, y):
                bad.append(f"{what}{jax.tree_util.keystr(kp)} "
                           f"max|diff|={np.abs(x - y).max():.3e}")
    say("sharded", f"{n} devices: params and inverses bitwise equal: "
                   f"{not bad}" + ("" if not bad else " " + "; ".join(bad[:8])))
    _check(not bad, "sharded refresh differs from serial")
    say("sharded", "ok")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded-refresh phase on a "
                         "(4, 1) mesh")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    say("smoke", f"devices={len(devs)} kind={dev.device_kind} "
                 f"jax={jax.__version__} compile_cache={cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        train_phase()
        parity_phase()
        serve_phase()
    say("smoke", f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
