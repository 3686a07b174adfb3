"""Subprocess body for ``tests/test_mesh.py``'s four-device check.

Runs in its OWN process because the simulated device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) must be set before
jax first initializes its backend; the test process has already imported
jax with one device.

The workload is a 3-stage ``tanh(x @ w)`` chain where ``w`` is a
PER-MESSAGE weight field, so each burst is a batch of independent GEMMs.
It is FMA-stable, so every execution path is bit-comparable.  Three variants
of the SAME fused unit are built through the real DSL + fusion pass:

* **sharded**: the mesh path (:func:`repro.core.fusion.fusion_mesh` live,
  padded bursts divide the data axis);
* **batched**: ``DATAX_FUSION_MESH=0``, the single-device vmapped program,
  identical except for partitioning;
* **host**: ``DATAX_FUSION_JIT=never``, the host-composed chain, the ground
  truth the device paths must match bit for bit.

Prints one JSON dict on stdout: the device counts, the fused unit's
``sharded_bursts`` counter (proof the mesh path ran) and ``bit_identical``.

Usage:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tests/workers/mesh_worker.py
"""
from __future__ import annotations

import json
import os
import sys

# must be decided before `import jax` anywhere below
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["DATAX_FUSION_JIT"] = "always"

import numpy as np  # noqa: E402

D = 128          # per-message x and w are both (D, D)
BURST = 64       # messages per process_batch call (pad == BURST, divisible)


def _build_process(app_factory):
    """DSL app -> the fused unit's live ``process`` callable."""
    from repro.core import fusion
    from repro.core.sdk import LogicContext

    application = app_factory().build()
    fused = fusion.fuse_application(application)
    unit = next(a for a in fused.analytics_units if a.fused_stages)
    ctx = LogicContext({}, db=None, instance_id="mesh-worker")
    return unit.logic(ctx)


def _app_factory():
    from repro.core import App, ShardSpec, StreamSchema
    import jax.numpy as jnp

    tensor = StreamSchema.device(
        x=((D, D), "float32", ShardSpec((None, None))),
        w=((D, D), "float32"))

    def step(p):
        x = jnp.tanh(p["x"] @ p["w"])
        return {"x": jnp.tanh(x @ p["w"]), "w": p["w"]}

    def make():
        app = App("mesh-worker")

        @app.driver(emits=tensor)
        def frames(ctx):
            return iter(())  # driven directly via process_batch below

        (app.sense("frames", frames)
            .map(step, emits=tensor, device=True, name="proj1")
            .map(step, emits=tensor, device=True, name="proj2")
            .map(step, emits=tensor, device=True, name="proj3"))
        return app

    return make


def main() -> int:
    import jax

    make = _app_factory()
    rng = np.random.default_rng(1)
    burst = [{"x": rng.standard_normal((D, D)).astype(np.float32),
              "w": rng.standard_normal((D, D)).astype(np.float32)}
             for _ in range(BURST)]

    sharded = _build_process(make)
    sharded_out = sharded.process_batch("worker", burst)

    os.environ["DATAX_FUSION_MESH"] = "0"
    batched_out = _build_process(make).process_batch("worker", burst)

    os.environ["DATAX_FUSION_JIT"] = "never"
    host_out = _build_process(make).process_batch("worker", burst)

    identical = all(
        np.array_equal(np.asarray(s["x"]), np.asarray(b["x"]))
        and np.array_equal(np.asarray(s["x"]), np.asarray(h["x"]))
        for s, b, h in zip(sharded_out, batched_out, host_out))

    print(json.dumps({
        "devices": jax.local_device_count(),
        "mesh_devices": sharded.stats["mesh_devices"],
        "sharded_bursts": sharded.stats["sharded_bursts"],
        "bit_identical": bool(identical),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
