"""Training launcher.

CPU-scale:
  python -m repro.launch.train --arch llama3.2-1b --reduced --steps 20 \
      --global_batch 8 --seq 64

Any registered optimizer races through the same trainer loop:
  python -m repro.launch.train --arch llama3.2-1b --reduced \
      --optimizer adam --lr 1e-3

TPU-pod scale (real deployment): drop --reduced, pass --mesh production
[--multi_pod]; the same code paths lower onto the 16x16 / 2x16x16 meshes the
dry-run validates.
"""
from __future__ import annotations

import argparse
from types import SimpleNamespace

import jax

from repro import obs as obs_mod
from repro import optimizers
from repro.configs import get_config, get_reduced_config
from repro.configs.base import KFACConfig, ObsConfig, TrainConfig
from repro.data.pipeline import (SyntheticLMData, make_audio_batch,
                                 make_vlm_batch)
from repro.kernels.backend import on_tpu
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.lm import LM
from repro.training.checkpoint import Checkpointer
from repro.training.trainer import Trainer
from repro.utils.compile_cache import enable_compile_cache


class _ArchData:
    """Wraps the token pipeline with the arch's raw modality inputs
    (images / mel frames; the model's own conv stems embed them)."""

    def __init__(self, cfg, base):
        self.cfg, self.base = cfg, base

    def batch(self, step):
        b = self.base.batch(step)
        if self.cfg.frontend == "patch":
            b = make_vlm_batch(b, self.cfg.image_size,
                               self.cfg.image_channels, self.base.mesh, step)
        if self.cfg.frontend == "audio":
            b = make_audio_batch(b, self.cfg.n_mels,
                                 2 * self.cfg.encoder_seq, self.base.mesh,
                                 step)
        return b


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global_batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", choices=["none", "local", "production"],
                    default="none")
    ap.add_argument("--multi_pod", action="store_true")
    ap.add_argument("--ckpt_dir", default="")
    ap.add_argument("--optimizer", default="kfac",
                    choices=["kfac", "sgd_momentum", "adam"])
    ap.add_argument("--lr", type=float, default=1e-3,
                    help="learning rate for the first-order baselines")
    ap.add_argument("--lambda_init", type=float, default=10.0)
    ap.add_argument("--inv_mode", default="blkdiag",
                    choices=["blkdiag", "tridiag", "eigen"])
    ap.add_argument("--refresh_mode", default="serial",
                    choices=["serial", "staggered", "sharded", "overlap"],
                    help="how the T3 inverse refresh executes: serially, "
                         "staggered over T3 steps, block-parallel over the "
                         "mesh, or asynchronously double-buffered "
                         "(repro.distributed; docs/distributed.md)")
    ap.add_argument("--kernel_backend", default=None,
                    choices=["xla", "pallas"],
                    help="curvature-block kernels (default: pallas on one "
                         "TPU without a mesh, xla otherwise)")
    ap.add_argument("--tau1", type=float, default=1.0)
    ap.add_argument("--obs", action="store_true",
                    help="enable telemetry: per-step/stage timings, "
                         "refresh events, end-of-run snapshot "
                         "(docs/observability.md)")
    ap.add_argument("--obs_jsonl", default="",
                    help="JSONL event log path (implies --obs)")
    ap.add_argument("--obs_console_every", type=int, default=0,
                    help="print the telemetry snapshot every N steps")
    return ap.parse_args(argv)


def build(args):
    """Model, optimizer, trainer, data and initial params for ``args`` —
    everything :func:`main` runs, for callers that drive it themselves."""
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    mesh = {"none": lambda: None, "local": make_local_mesh,
            "production": lambda: make_production_mesh(
                multi_pod=args.multi_pod)}[args.mesh]()

    # one shared Obs across the optimizer pipeline and the trainer: the
    # kfac_step / refresh events and the train_step events land in one
    # registry and one JSONL log
    ocfg = ObsConfig(enabled=args.obs or bool(args.obs_jsonl),
                     jsonl_path=args.obs_jsonl,
                     console_every=args.obs_console_every)
    obs = obs_mod.Obs(ocfg)

    # the kernels are single-device programs: under a mesh, XLA would
    # gather their operands onto every device, so meshes default to xla
    backend = args.kernel_backend or (
        "pallas" if on_tpu() and mesh is None else "xla")
    kcfg = KFACConfig(lambda_init=args.lambda_init, inv_mode=args.inv_mode,
                      refresh_mode=args.refresh_mode, tau1=args.tau1, t3=5,
                      kernel_backend=backend, obs=ocfg)
    tcfg = TrainConfig(steps=args.steps,
                       checkpoint_dir=args.ckpt_dir or "/tmp/repro_ckpt",
                       checkpoint_every=max(10, args.steps // 2),
                       obs=ocfg)
    lm = LM(cfg, kcfg, mesh)
    opt = (optimizers.kfac(lm, kcfg, mesh, obs=obs)
           if args.optimizer == "kfac"
           else optimizers.get(args.optimizer, lm, lr=args.lr))
    params = lm.init_params(jax.random.PRNGKey(0))
    data = _ArchData(cfg, SyntheticLMData(cfg.vocab_size, args.seq,
                                          args.global_batch, mesh))
    ckpt = Checkpointer(tcfg.checkpoint_dir) if args.ckpt_dir else None
    trainer = Trainer(lm, opt, tcfg, mesh, ckpt, obs=obs)
    return SimpleNamespace(cfg=cfg, mesh=mesh, lm=lm, opt=opt, obs=obs,
                           params=params, data=data, trainer=trainer)


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    run = build(args)
    print(f"[train] arch={run.cfg.name} params={run.lm.n_params():,} "
          f"optimizer={run.opt.name}")
    result = run.trainer.fit(run.params, run.data, args.steps)
    hist = result["history"]
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}"
          f" in {result['seconds']:.1f}s")
    obs = run.obs
    if obs.enabled:
        # the end-of-run stats line IS the obs snapshot — one formatting
        # path (repro.obs.export.console_summary) for every launcher
        print(obs.summary(title="train"))
        obs.close()
    return result


if __name__ == "__main__":
    main()
