"""Training loop with the full production spine: prefetching data pipeline,
jitted step, checkpoint/restart, failure injection + replay recovery,
straggler detection, telemetry, and the KERMIT autonomic hook (MAPE-K
Execute = re-jit with the tunables the plug-in selects).

The autonomic integration runs through :class:`repro.kermit.KermitSession`:
the Trainer binds a measured-step ``CallableExecutor`` (Execute phase) if the
session has none, subscribes to the typed event stream instead of polling
``events``, and calls ``session.step(sample)`` — no objective threading.  A
legacy ``AutonomicManager`` is still accepted and unwrapped to its session.

Runs reduced configs on CPU end-to-end; the same loop drives TPU meshes (the
step builder and sharding rules are mesh-agnostic).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional, Union

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec, Tunables, DEFAULT_TUNABLES
from repro.core.autonomic import AutonomicManager
from repro.data.pipeline import TokenPipeline
from repro.kermit import CallableExecutor, EventKind, KermitSession
from repro.models import model as M
from repro.optim.adamw import OptConfig
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.fault import (FailureInjector, SimulatedNodeFailure,
                                 StragglerDetector)
from repro.runtime.telemetry import StepStats, TelemetryEmitter
from repro.sharding import rules
from repro.train.step import init_train_state, make_train_step


@dataclass
class RunReport:
    steps_done: int = 0
    losses: list = field(default_factory=list)
    step_times: list = field(default_factory=list)
    failures_recovered: int = 0
    straggler_events: int = 0
    retunes: list = field(default_factory=list)
    analysis_events: int = 0
    final_tunables: Optional[dict] = None


class Trainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeSpec,
                 oc: OptConfig = OptConfig(),
                 tun: Tunables = DEFAULT_TUNABLES, *,
                 mesh=None, ckpt_dir: str | Path | None = None,
                 ckpt_every: int = 20,
                 autonomic: Optional[Union[KermitSession,
                                           AutonomicManager]] = None,
                 injector: Optional[FailureInjector] = None,
                 seed: int = 0):
        self.cfg, self.shape, self.oc = cfg, shape, oc
        self.tun = tun
        self.mesh = mesh
        rules.set_mesh(mesh)
        # accept the new session or the deprecated manager shim; all loop
        # logic below runs on the session API
        self.autonomic = autonomic.session \
            if isinstance(autonomic, AutonomicManager) else autonomic
        self.injector = injector
        self.straggler = StragglerDetector()
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.seed = seed

        self.state = init_train_state(jax.random.PRNGKey(seed), cfg, oc, tun)
        self.pipeline = TokenPipeline(cfg, shape, seed=seed,
                                      prefetch=tun.prefetch)
        self.step_num = 0
        self.infeasible = 0      # candidates measured_objective found OOM
        self._rebuild()
        n_active = sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(self.state["params"]))
        self.telemetry = TelemetryEmitter(
            seq_len=shape.seq_len, global_batch=shape.global_batch,
            model_flops_per_step=6.0 * n_active * shape.seq_len *
            shape.global_batch,
            root=self.autonomic.db.root
            if self.autonomic and self.autonomic.db.root else None)

    def _rebuild(self):
        fn = make_train_step(self.cfg, self.oc, self.tun)
        self._step = jax.jit(fn, donate_argnums=(0,) if self.tun.donate else ())

    # -- objective for the Explorer (measured trial steps) ---------------------

    def measured_objective(self, repeats: int = 1):
        """Median wall time of ``repeats`` trial steps under a candidate.
        A candidate that runs out of device memory is infeasible: it costs
        ``inf`` and counts in ``self.infeasible``.  Any other error (a
        compile error, a bug) propagates."""
        batch = self.pipeline._make(0)
        batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}

        def objective(tun: Tunables) -> float:
            if "ef" not in self.state and tun.grad_compression:
                tun = tun.replace(grad_compression=False)
            fn = jax.jit(make_train_step(self.cfg, self.oc, tun))
            try:
                s, _ = fn(self.state, batch)           # compile + warm
                jax.block_until_ready(s)
                ts = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    s, _ = fn(self.state, batch)
                    jax.block_until_ready(s)
                    ts.append(time.perf_counter() - t0)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                self.infeasible += 1
                return float("inf")
            return float(np.median(ts))
        return objective

    # -- recovery ---------------------------------------------------------------

    def _recover(self):
        assert self.ckpt is not None, "failure without checkpointing enabled"
        template = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(self.seed), self.cfg,
                                     self.oc, self.tun))
        state, meta = self.ckpt.restore(template)
        if state is None:
            state = init_train_state(jax.random.PRNGKey(self.seed), self.cfg,
                                     self.oc, self.tun)
            meta = {"step": 0, "pipeline": {"seed": self.seed, "step": 0}}
        self.state = state
        self.step_num = meta["step"]
        self.pipeline.close()
        self.pipeline = TokenPipeline.restore(self.cfg, self.shape,
                                              meta["pipeline"],
                                              prefetch=self.tun.prefetch)

    # -- main loop ----------------------------------------------------------------

    def run(self, steps: int) -> RunReport:
        rep = RunReport()
        unsubscribe = None
        if self.autonomic is not None:
            # Execute phase: measured trial steps of THIS trainer.  Rebind
            # when unset or owned by a previous Trainer run (schedules reuse
            # one session across phases with different model shapes).
            ex = self.autonomic.executor
            if ex is None or getattr(ex, "_trainer_owned", False):
                ex = CallableExecutor(self.measured_objective(
                    self.autonomic.config.execute.measure_repeats))
                ex._trainer_owned = True
                self.autonomic.bind_executor(ex, replace=True)
            # event subscription instead of polling session.events
            def _on_analysis(ev, _rep=rep):
                _rep.analysis_events += 1
            unsubscribe = self.autonomic.subscribe(EventKind.ANALYSIS,
                                                   _on_analysis)
        try:
            return self._run_loop(steps, rep)
        finally:
            # sessions outlive Trainers (multi-phase schedules): the handler
            # must not leak into later phases even on an aborted run
            if unsubscribe is not None:
                unsubscribe()

    def _run_loop(self, steps: int, rep: RunReport) -> RunReport:
        # progress-based: failures + replays still land exactly on ``steps``
        while self.step_num < steps:
            try:
                if self.injector:
                    self.injector.check(self.step_num)
                batch = self.pipeline.next()
                t0 = time.perf_counter()
                self.state, metrics = self._step(self.state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0

                loss = float(metrics["loss"])
                rep.losses.append(loss)
                rep.step_times.append(dt)
                ev = self.straggler.observe(self.step_num, dt)
                if ev:
                    rep.straggler_events += 1

                sample = self.telemetry.emit(StepStats(
                    step_time=dt,
                    tokens=self.shape.seq_len * self.shape.global_batch,
                    loss=loss, grad_norm=float(metrics["grad_norm"]),
                    host_wait=self.pipeline.host_wait_s))

                if self.autonomic is not None:
                    new_tun = self.autonomic.step(sample)
                    if new_tun != self.tun:
                        if "ef" not in self.state:
                            new_tun = new_tun.replace(grad_compression=False)
                        self.tun = new_tun
                        rep.retunes.append((self.step_num,
                                            new_tun.as_dict()))
                        self._rebuild()

                self.step_num += 1
                rep.steps_done = self.step_num
                if self.ckpt and self.step_num % self.ckpt_every == 0:
                    self.ckpt.save(self.step_num, self.state, {
                        "pipeline": self.pipeline.state(),
                        "tunables": self.tun.as_dict()})
            except SimulatedNodeFailure:
                rep.failures_recovered += 1
                self._recover()
        rep.final_tunables = self.tun.as_dict()
        self.pipeline.close()
        return rep
