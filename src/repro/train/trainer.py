"""Trainer — the training loop as a DataX application.

The training run is literally a stream application on the platform
(DESIGN.md §3):

  corpus (sensor) -> packer (AU) -> batcher (AU) ->
      train_step (DEVICE AU, pjit on the mesh) -> {metrics stream,
      checkpoint actuator}

The Operator owns every host stage (restarts crashes, autoscales the packer,
replaces stragglers); the Trainer drives the device AU: pulls batch messages,
device_puts them against the derived shardings, steps, publishes metrics,
checkpoints asynchronously, and honors preemption.  Fault behaviours
(preemption-save, straggler flagging, restore-on-start) are all exercised by
tests/test_fault.py.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import models
from repro.configs.base import ModelConfig, RunConfig
from repro.core import (AnalyticsUnitSpec, DriverSpec, Operator, SensorSpec,
                        StreamSpec)
from repro.data import corpus as corpus_mod
from repro.data import pipeline as pipe
from repro.distributed import sharding as shard
from repro.launch.mesh import make_host_mesh

from . import optimizer as opt
from . import steps as steps_mod
from .checkpoint import CheckpointManager
from .fault import PreemptionHandler, StepTimeMonitor


@dataclasses.dataclass
class TrainerConfig:
    global_batch: int = 8
    seq_len: int = 256
    ckpt_every: int = 50
    log_every: int = 10
    total_steps: int = 1000
    workdir: str = "/tmp/repro-train"
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, run: RunConfig, tcfg: TrainerConfig,
                 mesh=None, operator: Operator | None = None,
                 deploy_pipeline: bool = True, batch_stream: str = "batches"):
        """``deploy_pipeline=False`` skips the built-in v1 spec-style data
        pipeline: the caller deploys its own (e.g. a v2 fluent-DSL app, see
        examples/train_lm.py) onto ``operator`` and the Trainer just
        subscribes to ``batch_stream`` — the paper's stream-reuse claim
        applied to the training loop itself."""
        self.cfg = cfg
        self.run = run
        self.tcfg = tcfg
        self.mesh = mesh or make_host_mesh()
        self.op = operator or Operator(reconcile_interval_s=0.2)
        self._own_operator = operator is None
        self.preemption = PreemptionHandler()
        self.monitor = StepTimeMonitor()
        self.ckpt = CheckpointManager(tcfg.workdir + "/ckpt")
        self.metrics_log: list[dict] = []
        self.step = 0
        if deploy_pipeline:
            self._deploy_pipeline()
        else:
            self._batch_sub = self.op.subscribe(batch_stream, name="trainer",
                                                maxsize=4)
        self._build_device_au()

    # ------------------------------------------------------------- pipeline
    def _deploy_pipeline(self) -> None:
        t = self.tcfg
        self.op.register_driver(DriverSpec(
            name="corpus", logic=corpus_mod.corpus_driver,
            config_schema=corpus_mod.CORPUS_CONFIG,
            output_schema=corpus_mod.CORPUS_SCHEMA))
        self.op.register_analytics_unit(AnalyticsUnitSpec(
            name="packer", logic=pipe.packer_au,
            config_schema=pipe.PACKER_CONFIG,
            output_schema=pipe.PACKED_SCHEMA, max_instances=4))
        self.op.register_analytics_unit(AnalyticsUnitSpec(
            name="batcher", logic=pipe.batcher_au,
            config_schema=pipe.BATCHER_CONFIG,
            output_schema=pipe.BATCH_SCHEMA, max_instances=1))
        self.op.register_sensor(SensorSpec(
            name="docs", driver="corpus",
            config={"vocab": self.cfg.vocab, "seed": t.seed}), start=False)
        self.op.create_stream(StreamSpec(
            name="sequences", analytics_unit="packer", inputs=("docs",),
            config={"seq_len": t.seq_len}))
        # batcher must be a single instance (it accumulates across messages)
        self.op.create_stream(StreamSpec(
            name="batches", analytics_unit="batcher", inputs=("sequences",),
            config={"batch": t.global_batch}, fixed_instances=1))
        self.op.start()
        self._batch_sub = self.op.subscribe("batches", name="trainer",
                                            maxsize=4)
        self.op.start_pending_sensors()

    # ------------------------------------------------------------ device AU
    def _build_device_au(self) -> None:
        batch_shape = {
            "tokens": jax.ShapeDtypeStruct(
                (self.tcfg.global_batch, self.tcfg.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct(
                (self.tcfg.global_batch, self.tcfg.seq_len), jnp.int32),
        }
        self.train_step, (params_shape, opt_shape) = steps_mod.jit_train_step(
            self.cfg, self.run, self.mesh, batch_shape,
            total_steps=self.tcfg.total_steps)
        self.params_shape = params_shape
        pspecs = shard.param_specs(params_shape, self.cfg, self.run, self.mesh)
        self.param_shardings = shard.to_shardings(pspecs, self.mesh)
        self.batch_shardings = shard.to_shardings(
            shard.batch_specs(batch_shape, self.mesh), self.mesh)

    # ------------------------------------------------------------ lifecycle
    def init_or_restore(self) -> None:
        state_like = {
            "params": self.params_shape,
            "opt": steps_mod.abstract_opt_state(self.params_shape, self.run),
        }
        latest = self.ckpt.latest_step()
        if latest is not None:
            state, manifest = self.ckpt.restore(state_like)
            self.params, self.opt_state = state["params"], state["opt"]
            self.step = manifest["step"]
            return
        with jax.default_device(jax.devices()[0]):
            self.params = models.init(
                jax.random.PRNGKey(self.tcfg.seed), self.cfg)
            self.opt_state = opt.init_opt_state(self.params, self.run)
        self.params = jax.device_put(self.params, self.param_shardings)

    def _next_batch(self, timeout: float = 30.0) -> dict | None:
        msg = self._batch_sub.next(timeout=timeout)
        if msg is None:
            return None
        return jax.device_put(
            {"tokens": msg.payload["tokens"], "labels": msg.payload["labels"]},
            self.batch_shardings)

    # ------------------------------------------------------------------- run
    def run_steps(self, n: int) -> list[dict]:
        out = []
        for _ in range(n):
            if self.preemption.preempted:
                self.ckpt.save(self.step, {"params": self.params,
                                           "opt": self.opt_state},
                               blocking=True, meta={"preempted": True})
                break
            batch = self._next_batch()
            if batch is None:
                break
            t0 = time.monotonic()
            self.params, self.opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.monotonic() - t0
            self.step += 1
            straggler = self.monitor.record(self.step, dt)
            metrics.update(step=self.step, step_time_s=dt,
                           straggler=straggler)
            self.metrics_log.append(metrics)
            out.append(metrics)
            if self.step % self.tcfg.ckpt_every == 0:
                self.ckpt.save(self.step, {"params": self.params,
                                           "opt": self.opt_state})
        return out

    def close(self) -> None:
        self.ckpt.wait()
        if self._own_operator:
            self.op.shutdown()
