"""Claim (tentpole PR 8): mesh-sharded fused bursts beat single-device ones.

The batched fused program (PR 5) amortizes per-message dispatch into one
vmapped call per burst — but still runs that call on ONE device.  When a
mesh is visible (:func:`repro.core.fusion.fusion_mesh`) the burst's leading
batch axis is partitioned across it with ``NamedSharding``
(:func:`repro.kernels.ops.jit_chain_sharded`, specs derived from the stream
schema's :class:`~repro.core.schema.ShardSpec` hints), so each device runs
its slice of the same program.

The measurement happens in a SUBPROCESS (``mesh_worker.py``) with
``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the worker
simulates a four-device mesh on the CPU, exactly as the tests do.  It never
touches an accelerator, so it cannot contend with this process for a chip
that an earlier benchmark already initialised.  The worker builds the
same 3-stage matmul chain through
the real DSL + fusion pass and reports sharded vs single-device-batched
``process_batch`` throughput plus bit-identity of both against the
host-composed chain.

CI gates on BENCH_mesh.json: ``speedup`` (sharded over batched) >= 1,
``bit_identical`` true, and ``sharded_bursts`` > 0 (the mesh path actually
executed, not silently fallen back).  No jax -> ``{"skipped": ...}`` and
the gate passes vacuously (minimal-deps leg).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from .common import emit

_REPO = pathlib.Path(__file__).resolve().parent.parent
WORKER = _REPO / "benchmarks" / "mesh_worker.py"
DEVICES = 4
TIMEOUT = 600


def run() -> dict:
    try:
        import jax  # noqa: F401
    except Exception:
        emit("mesh_sharded", 0.0, "skipped=no_jax")
        return {"skipped": "jax not importable"}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICES}"
    env["PYTHONPATH"] = str(_REPO / "src")
    env.pop("DATAX_FUSION_MESH", None)
    env.pop("DATAX_FUSION_JIT", None)
    proc = subprocess.run(
        [sys.executable, str(WORKER), "--devices", str(DEVICES)],
        env=env, cwd=str(_REPO), capture_output=True, text=True,
        timeout=TIMEOUT)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh_worker failed:\n{proc.stderr}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    emit("mesh_sharded_burst", 1e6 / data["sharded_msgs_per_s"],
         f"msgs_per_s={data['sharded_msgs_per_s']:.0f} "
         f"devices={data['devices']}")
    emit("mesh_batched_burst", 1e6 / data["batched_msgs_per_s"],
         f"msgs_per_s={data['batched_msgs_per_s']:.0f} devices=1")
    emit("mesh_speedup", 0.0,
         f"sharded_over_batched={data['speedup']:.2f}x "
         f"bit_identical={data['bit_identical']}")
    return data
