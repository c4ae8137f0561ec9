"""Fault-tolerant, optimizer-agnostic training loop.

The trainer knows nothing about any particular optimizer: per step it
calls ``opt.update(None, state, params, batch, rng)`` and lets the
optimizer run its own schedule (for K-FAC that is paper Algorithm 2 —
stats+grads every step, inverses every T3 and for k<=3, gamma sweep every
T2, lambda rule every T1 — all driven off the step counter in the state by
``repro.optimizers.kfac.KFACPipeline``).  Any
:class:`repro.core.transform.Optimizer` races through the same loop;
legacy ``repro.core.kfac.KFAC`` engines are wrapped automatically.

Fault tolerance:
  * atomic async checkpoints every `checkpoint_every` (params + full
    optimizer state + step), auto-restore on construction;
  * SIGTERM/SIGINT preemption hook → synchronous checkpoint, clean exit;
  * non-finite guard: a NaN/Inf update is *skipped* (params untouched,
    ``opt.reject`` applied — K-FAC raises damping and clears momentum)
    rather than poisoning the run.  An optimizer whose metrics carry a
    device flag ``finite`` (K-FAC) applies the skip in its own programs
    and the trainer keeps what it returns; for the others the trainer
    reads the check on the host;
  * elastic restart: checkpoints restore onto any mesh (see elastic.py).

One step in flight: a step's scalar metrics are fetched in one transfer
after the next step is dispatched, so ``history``, the rejected count and
the log lines run a step behind the device; ``fit`` reads the last row
and blocks on the params and state it returns before returning.
"""
from __future__ import annotations

import contextlib
import dataclasses
import signal
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.obs import CompileClock
from repro.optimizers import as_optimizer
from repro.training.checkpoint import Checkpointer
from repro.utils import tree as T


# the step's key in one compiled dispatch: the eager fold_in pays its
# Python wrapper and conversions on the host at every step
_fold_in = jax.jit(jax.random.fold_in)


@dataclasses.dataclass
class _Row:
    """A dispatched step whose metrics the host has not read yet."""

    step: int
    metrics: dict                 # scalar metrics, on the device
    finite: Any                   # guard flag: device array or host bool
    wall_s: Optional[float] = None            # enabled only
    syncs: float = 0.0                        # reads made dispatching it
    compiles: int = 0


class Trainer:
    def __init__(self, model, opt, train_cfg: TrainConfig, mesh=None,
                 checkpointer: Optional[Checkpointer] = None, obs=None):
        from repro import obs as obs_mod
        self.model = model
        self.opt = as_optimizer(opt)
        self.tc = train_cfg
        self.mesh = mesh
        self.ckpt = checkpointer
        # telemetry (repro.obs): obs=None reads train_cfg.obs; launchers
        # pass the same Obs they handed the optimizer so train_step and
        # kfac_step events land in one log.  Counters stay live even when
        # disabled (cheap host ints); timing/events only when enabled.
        self.obs = obs_mod.from_config(obs if obs is not None
                                       else train_cfg.obs)
        self._c_rejected = self.obs.counter("train/rejected_steps")
        self._c_steps = self.obs.counter("train/steps")
        # device->host reads at the loop's sync sites (always live); the
        # optimizer counts its own sites under the same name
        self._c_finite_syncs = self.obs.counter(
            "train/host_syncs", {"site": "train/finite_check"})
        self._c_metric_syncs = self.obs.counter(
            "train/host_syncs", {"site": "train/metrics_to_host"})
        # steps whose non-finite guard ran in the optimizer's programs
        self._c_device_guard = self.obs.counter("train/device_guard_steps")
        self._preempted = False
        self._bundle_writer = None
        self._install_handlers()

    # ------------------------------------------------------------------
    def _install_handlers(self):
        def handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not on main thread (tests)

    # ------------------------------------------------------------------
    def fit(self, params, data, steps: int, start_step: int = 0,
            log=print) -> Dict[str, Any]:
        batch0 = data.batch(start_step)
        state = self.opt.init(params, batch0)

        # auto-restore
        if self.ckpt is not None:
            got_step, got = self.ckpt.restore({"params": params,
                                               "state": state})
            if got_step is not None:
                params, state = got["params"], got["state"]
                start_step = got_step
                log(f"[trainer] restored checkpoint at step {got_step}")

        history = []
        t_start = time.time()
        fused = bool(getattr(getattr(self.opt, "engine", None),
                             "fused", False))
        # a step's scalar metrics are read one step late, in one transfer,
        # while the next step runs: the loop keeps one step in flight
        pending = None
        key = jax.random.PRNGKey(self.tc.seed)
        # compile events are counted per step only when enabled; the
        # listener is removed when the loop ends, by an exception too
        clock = (CompileClock() if self.obs.enabled
                 else contextlib.nullcontext())
        with clock:
            for step in range(start_step, steps):
                if self.obs.enabled:
                    syncs0, compiles0 = self._host_syncs(), clock.compiles
                with self.obs.span("train/step_inputs"):
                    batch = data.batch(step)
                    rng = _fold_in(key, step)

                # per-step wall time: host-side span blocking on the
                # produced params at close (enabled only — disabled is the
                # shared no-op span: no clock reads, no extra sync, same
                # jitted programs)
                with self.obs.span("train/step",
                                   block=lambda: new_params) as span:
                    new_params, state, metrics = self.opt.update(
                        None, state, params, batch, rng)
                self._c_steps.inc()

                # non-finite guard: skip poisoned updates, let the
                # optimizer react (K-FAC: 4x damping + momentum reset).
                # An optimizer whose metrics carry the device flag
                # "finite" applied it in its own programs.
                finite = metrics.get("finite")
                if finite is None:
                    finite = self._host_guard(new_params, metrics)
                    if finite:
                        params = new_params
                    else:
                        state = self.opt.reject(state)
                else:
                    self._c_device_guard.inc()
                    params = new_params

                # swap hook: optimizers running asynchronous side
                # computations (K-FAC refresh_mode="overlap") commit any
                # finished buffer here without blocking the step loop
                if self.opt.poll is not None:
                    state = self.opt.poll(state)

                row = _Row(step, {k: v for k, v in metrics.items()
                                  if k != "finite" and jnp.ndim(v) == 0},
                           finite)
                if self.obs.enabled:
                    row.wall_s = span.seconds
                    row.syncs = self._host_syncs() - syncs0
                    row.compiles = clock.compiles - compiles0
                self._read_row(pending, history, log, fused)
                pending = row

                if self.ckpt is not None and (
                        (step + 1) % self.tc.checkpoint_every == 0):
                    bundle_ref = self._export_bundle(step + 1, state, log)
                    self.ckpt.save(step + 1,
                                   {"params": params, "state": state},
                                   curvature_bundle=bundle_ref)

                if self._preempted:
                    self._read_row(pending, history, log, fused)
                    pending = None
                    log(f"[trainer] preempted at step {step}; checkpointing")
                    if self.ckpt is not None:
                        self.ckpt.save(step + 1, {"params": params,
                                                  "state": state}, block=True)
                    break
            self._read_row(pending, history, log, fused)

        # the caller's clock stops once every step it counts has run
        jax.block_until_ready((params, state))
        if self.ckpt is not None:
            self.ckpt.wait()
        if self._bundle_writer is not None:
            self._bundle_writer.wait()
        return {"params": params, "state": state, "history": history,
                "seconds": time.time() - t_start}

    def _host_guard(self, new_params, metrics) -> bool:
        """The guard for an optimizer without a device flag: read whether
        the new params and the update's norm are finite."""
        with self.obs.span("train/finite_check"):
            reads = 1
            finite = bool(T.tree_isfinite(new_params))
            if finite:
                delta = metrics.get("delta_norm", 0.0)
                reads += isinstance(delta, jax.Array)
                finite = bool(np.isfinite(float(delta)))
        self._c_finite_syncs.inc(reads)
        return finite

    def _read_row(self, row: Optional[_Row], history: list, log,
                  fused: bool):
        """Fetch a step's scalar metrics and guard flag in one transfer,
        then record the step: history, rejected count, log lines and
        (enabled) its ``train_step`` event."""
        if row is None:
            return
        with self.obs.span("train/metrics_to_host"):
            scalars, finite = jax.device_get((row.metrics, row.finite))
            hist = {k: float(scalars[k]) for k in row.metrics}
        transfers = int(any(isinstance(v, jax.Array) for v in
                            jax.tree.leaves((row.metrics, row.finite))))
        self._c_metric_syncs.inc(transfers)
        history.append(hist)
        if not finite:
            self._c_rejected.inc()
            log(f"[trainer] step {row.step}: non-finite update SKIPPED "
                f"(rejected by {self.opt.name})")
        if self.obs.enabled:
            with self.obs.span("train/emit"):
                self._emit_step(row.step, row.wall_s, hist,
                                rejected=not finite, fused=fused,
                                host_syncs=row.syncs + transfers,
                                compiles=row.compiles)
        if row.step % self.tc.log_every == 0:
            extras = " ".join(f"{k}={hist[k]:.2e}" for k in ("alpha", "lam")
                              if k in hist)
            log(f"[trainer] step {row.step}: "
                f"loss={hist['loss']:.4f} {extras}".rstrip())

    # ------------------------------------------------------------------
    def _host_syncs(self) -> float:
        """Device->host reads made so far at every sync site."""
        return sum(c.value for c in self.obs.registry.find(
            "train/host_syncs"))

    def _emit_step(self, step: int, wall_s, hist_row: dict, *,
                   rejected: bool, fused: bool, host_syncs: float,
                   compiles: int):
        """One ``train_step`` JSONL event + gauges (enabled path only).
        The optimizer's scalar metrics ride along under their own names
        (lam / gamma / alpha / rho / nu / staleness when present), with
        the step's device->host reads and backend compiles."""
        def fin(x):      # a rejected step's metrics may be NaN/Inf; the
            return float(x) if np.isfinite(x) else None   # schema is finite-only
        extras = {k: fin(hist_row[k])
                  for k in ("lam", "gamma", "alpha", "rho", "nu",
                            "staleness", "grad_norm", "delta_norm")
                  if k in hist_row}
        self.obs.emit("train_step", step=step,
                      loss=fin(hist_row.get("loss", 0.0)),
                      wall_s=wall_s, rejected=rejected,
                      fused_stats=fused, host_syncs=int(host_syncs),
                      compiles=compiles, **extras)
        self.obs.counter("train/compiles").inc(compiles)
        self.obs.gauge("train/loss").set(hist_row.get("loss", 0.0))
        if "lam" in hist_row:
            self.obs.gauge("train/lambda").set(hist_row["lam"])
        if "gamma" in hist_row:
            self.obs.gauge("train/gamma").set(hist_row["gamma"])
        self.obs.maybe_console(step, title="train")

    # ------------------------------------------------------------------
    def _export_bundle(self, step: int, state, log) -> Optional[str]:
        """Non-blocking curvature-bundle export at checkpoint steps
        (``TrainConfig.curvature_every``; 0 = off).  Snapshotting only
        captures immutable device-array references on the training thread
        (the ``OverlapController`` idea); serialization runs on the
        :class:`~repro.curvature.bundle.BundleWriter` daemon thread.
        Returns the manifest-relative bundle path, or None."""
        import os

        if (not self.tc.curvature_every
                or step % self.tc.curvature_every != 0):
            return None
        engine = getattr(self.opt, "engine", None)
        if engine is None or not getattr(engine, "blocks", None):
            return None   # first-order baselines carry no curvature
        from repro.curvature.bundle import BundleWriter, snapshot_bundle

        opt_state = state.inner if hasattr(state, "inner") else state
        bundle = snapshot_bundle(engine, opt_state)
        if bundle is None:
            return None
        if self._bundle_writer is None:
            self._bundle_writer = BundleWriter()
        rel = os.path.join("curvature", f"step_{step:08d}")
        self._bundle_writer.write_async(
            os.path.join(self.ckpt.dir, rel), bundle)
        log(f"[trainer] step {step - 1}: curvature bundle -> {rel}")
        return rel
