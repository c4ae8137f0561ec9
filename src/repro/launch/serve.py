"""Serving launcher: continuous batching over the paged-KV engine.

  python -m repro.launch.serve --arch smollm-135m --reduced --requests 6 \\
      --temperature 0.8 --top_k 40 --seed 7

The default decode route is paged: block-indexed paged attention on a TPU,
the einsum oracle over the gathered pages elsewhere (``--decode_route
gather`` selects the dense-gather oracle on any backend);
``--num_pages`` shrinks the page pool to exercise eviction/preemption.

``--uncertainty`` requests per-token Laplace predictive variance: pass
``--bundle <path>`` to load a training-exported curvature bundle
(``docs/influence.md``), or omit it to build an identity bundle from the
model's own block registry (fresh zero factors — a smoke-test posterior,
not a trained one).  Uncertainty stats print only when requested; without
the flag the engine and its outputs are identical to before.
"""
from __future__ import annotations

import argparse

import jax

from repro import obs as obs_mod
from repro.configs import get_config, get_reduced_config
from repro.models.lm import LM
from repro.obs import ObsConfig
from repro.serving.server import DECODE_ROUTES, Engine, Request
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max_len", type=int, default=128)
    ap.add_argument("--max_new", type=int, default=8)
    ap.add_argument("--decode_route", choices=DECODE_ROUTES, default="paged")
    ap.add_argument("--page_size", type=int, default=8)
    ap.add_argument("--num_pages", type=int, default=None,
                    help="page pool size; small values force "
                         "eviction/preemption under load")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples")
    ap.add_argument("--top_k", type=int, default=0)
    ap.add_argument("--top_p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed base (request i uses "
                         "seed+i); omit for the engine-shared RNG")
    ap.add_argument("--uncertainty", action="store_true",
                    help="request per-token Laplace predictive variance")
    ap.add_argument("--bundle", default=None,
                    help="curvature bundle path (with --uncertainty); "
                         "omit for an identity smoke-test bundle")
    ap.add_argument("--obs", action="store_true",
                    help="enable telemetry: queue/slot/page gauges, "
                         "TTFT & decode-gap histograms, JSONL events "
                         "(docs/observability.md)")
    ap.add_argument("--obs_jsonl", default="",
                    help="JSONL event log path (implies --obs)")
    args = ap.parse_args(argv)

    obs = obs_mod.Obs(ObsConfig(enabled=args.obs or bool(args.obs_jsonl),
                                jsonl_path=args.obs_jsonl))

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    lm = LM(cfg)
    params = lm.init_params(jax.random.PRNGKey(0))
    laplace = _build_laplace(lm, args) if args.uncertainty else None
    eng = Engine(lm, params, batch_slots=args.slots, max_len=args.max_len,
                 page_size=args.page_size, num_pages=args.num_pages,
                 decode_route=args.decode_route, laplace=laplace, obs=obs)
    reqs = [Request(uid=i, prompt=[(7 * i + j) % cfg.vocab_size
                                   for j in range(4 + i % 3)],
                    max_new=args.max_new, temperature=args.temperature,
                    top_k=args.top_k, top_p=args.top_p,
                    seed=None if args.seed is None else args.seed + i,
                    uncertainty=args.uncertainty)
            for i in range(args.requests)]
    rep = eng.run(reqs)
    for r in reqs:
        tag = f" (preempted x{r.preemptions})" if r.preemptions else ""
        if args.uncertainty and r.var:
            tag += (f" var[{min(r.var):.3g}..{max(r.var):.3g}]"
                    f" mean={sum(r.var) / len(r.var):.3g}")
        print(f"[serve] req {r.uid}: prompt={r.prompt} -> out={r.out}{tag}")
    assert all(r.done or r.out for r in reqs)
    print(f"[serve] {rep.steps} steps ({args.decode_route} route): "
          f"{len(rep.completed)} completed, "
          f"{len(rep.unfinished)} in flight, {len(rep.unserved)} queued, "
          f"{len(rep.failed)} rejected")
    # the stats line renders from the obs registry — the engine's always-
    # live counters — through the one shared formatting path
    print(obs.summary(title="serve"))
    if obs.enabled and rep.ttft_p50_ms is not None:
        print(f"[serve] ttft p50={rep.ttft_p50_ms:.2f}ms "
              f"p99={rep.ttft_p99_ms:.2f}ms")
    if args.uncertainty and rep.mean_token_variance is not None:
        print(f"[serve] mean per-token Laplace variance: "
              f"{rep.mean_token_variance:.4g}")
    obs.close()
    return rep


def _build_laplace(lm, args):
    """The Laplace head for --uncertainty: a trained bundle from disk, or
    an identity bundle (zero factors, gamma=1) as a smoke-test stand-in."""
    from repro.curvature import CurvatureBundle, LaplaceHead, load_bundle

    if args.bundle is not None:
        return LaplaceHead(load_bundle(args.bundle))
    from repro.configs.base import KFACConfig
    from repro.core.blocks import build_blocks

    name = "lm_head" if "lm_head" in lm.metas else "embed"
    meta = lm.metas[name]
    blk = build_blocks({name: meta}, KFACConfig())[name]
    eig = blk.eigen_state(blk.init_factors(), 1.0)
    return LaplaceHead(CurvatureBundle(
        step=0, lam=1.0, gamma=1.0, eta=0.0,
        metas={name: meta}, eigen={name: eig}))


if __name__ == "__main__":
    main()
