"""Mesh-sharded fused execution (PR 8): sharding, residency, autotune.

The multi-device half runs in a SUBPROCESS (benchmarks/mesh_worker.py with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) because the device
count must be fixed before jax initializes — this test process already
imported jax with one device.  The single-process half exercises the same
machinery in-process: burst PartitionSpecs, ResidentArray reuse rules, the
burst autotuner, and bit-identity of the sharded program on a 1-device mesh.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import ShardSpec, StreamSchema  # noqa: E402
from repro.core import fusion  # noqa: E402
from repro.core.fusion import (AUTOTUNE_STREAK, FusedStage,  # noqa: E402
                               ResidentArray, _resident_burst,
                               _to_device_batched, make_fused_logic)
from repro.core.sdk import LogicContext  # noqa: E402
from repro.distributed.sharding import burst_spec  # noqa: E402
from repro.kernels.ops import (jit_chain_batched,  # noqa: E402
                               jit_chain_sharded)

_REPO = pathlib.Path(__file__).resolve().parent.parent
WORKER = _REPO / "benchmarks" / "mesh_worker.py"

D = 16


def _stage_fn(w):
    return lambda p: {"x": jnp.tanh(p["x"] @ w)}


def _fused_process(n_stages=2, schema=None, max_batch=None, resident=False):
    rng = np.random.default_rng(0)
    stages = []
    for i in range(n_stages):
        fn = _stage_fn(rng.standard_normal((D, D)).astype(np.float32))

        def factory(ctx, fn=fn):
            return lambda stream, payload: fn(payload)

        stages.append(FusedStage(au_name=f"au{i}", stream_name=f"s{i}",
                                 factory=factory, config={}, kind="map",
                                 pure_fn=fn))
    if schema is None:
        schema = StreamSchema.device(x=((4, D), "float32"))
    ctx = LogicContext({}, db=None, instance_id="test")
    return make_fused_logic(stages, schema, max_batch=max_batch,
                            resident=resident)(ctx)


@pytest.fixture
def jit_always(monkeypatch):
    monkeypatch.setenv("DATAX_FUSION_JIT", "always")


def _payloads(n, rows=4):
    rng = np.random.default_rng(1)
    return [{"x": rng.standard_normal((rows, D)).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Multi-device: subprocess with 4 fake host devices
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_execution_on_four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(_REPO / "src")
    env.pop("DATAX_FUSION_MESH", None)
    proc = subprocess.run(
        [sys.executable, str(WORKER), "--rounds", "2"],
        env=env, cwd=str(_REPO), capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["devices"] == 4
    assert data["mesh_devices"] == 4
    assert data["sharded_bursts"] > 0      # the mesh path actually ran
    assert data["bit_identical"] is True   # vs single-device AND host chain


# ---------------------------------------------------------------------------
# fusion_mesh gating
# ---------------------------------------------------------------------------

def test_fusion_mesh_single_device_is_none():
    # this process sees one CPU device -> no mesh, no sharded path
    if jax.local_device_count() != 1:
        pytest.skip("test process has multiple devices")
    assert fusion.fusion_mesh() is None
    assert fusion.mesh_axis_names() == ()


def test_fusion_mesh_env_disable(monkeypatch):
    monkeypatch.setenv("DATAX_FUSION_MESH", "0")
    assert fusion.fusion_mesh() is None


# ---------------------------------------------------------------------------
# burst_spec: schema hints -> PartitionSpecs
# ---------------------------------------------------------------------------

def _mesh1():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.local_devices()[:1]), ("data",))


def test_burst_spec_leading_batch_axis():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh1()
    assert burst_spec(mesh, 8, (4, D), None) == P(("data",), None, None)
    # hint axes the mesh doesn't have replicate silently
    assert burst_spec(mesh, 8, (4, D), ShardSpec(("model", None))) \
        == P(("data",), None, None)
    # the data axis is spent on the batch dim -> not reused on trailing dims
    assert burst_spec(mesh, 8, (4, D), ShardSpec(("data", None))) \
        == P(("data",), None, None)


def test_burst_spec_divisibility():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh1()   # axis size 1 divides everything
    assert burst_spec(mesh, 7, (3,), None) == P(("data",), None)


# ---------------------------------------------------------------------------
# jit_chain_sharded: bit-identity on a 1-device mesh
# ---------------------------------------------------------------------------

def test_jit_chain_sharded_matches_batched():
    rng = np.random.default_rng(2)
    w = rng.standard_normal((D, D)).astype(np.float32)
    chain = [("map", _stage_fn(w))]
    batched = jit_chain_batched(chain)
    sharded = jit_chain_sharded(chain, _mesh1(), {})
    x = rng.standard_normal((8, 4, D)).astype(np.float32)
    out_b, keep_b = batched({"x": jnp.asarray(x)})
    out_s, keep_s = sharded({"x": x})
    assert np.array_equal(np.asarray(out_b["x"]), np.asarray(out_s["x"]))
    assert np.array_equal(np.asarray(keep_b), np.asarray(keep_s))


# ---------------------------------------------------------------------------
# ResidentArray: wrap/reuse rules
# ---------------------------------------------------------------------------

def test_resident_array_wrap_and_derivation():
    dev = jnp.zeros((4, 3))
    row = ResidentArray.wrap(np.ones(3), dev, 1)
    assert isinstance(row, np.ndarray)
    assert row._datax_dev is dev and row._datax_row == 1
    # views/slices/copies must NOT inherit residency
    assert row[1:]._datax_dev is None
    assert row.copy()._datax_dev is None
    assert (row * 2)._datax_dev is None


def test_resident_burst_reuse_requires_intact_rows():
    dev = jnp.arange(12.0).reshape(4, 3)
    rows = [ResidentArray.wrap(np.asarray(dev[i]), dev, i) for i in range(4)]
    assert _resident_burst(rows, 4) is dev
    # pad mismatch
    assert _resident_burst(rows, 8) is None
    # non-contiguous (a filtered row) breaks the link
    assert _resident_burst([rows[0], rows[2]], 4) is None
    # a plain ndarray row breaks the link
    assert _resident_burst([rows[0], np.asarray(dev[1])], 4) is None


def test_to_device_batched_reuses_resident(jit_always):
    dev = jnp.arange(24.0).reshape(4, 2, 3)
    payloads = [{"x": ResidentArray.wrap(np.asarray(dev[i]), dev, i)}
                for i in range(4)]
    stats = {"resident_links": 0}
    out = _to_device_batched(payloads, 4, stats)
    assert out["x"] is dev
    assert stats["resident_links"] == 1


def test_linked_segments_pass_resident_rows_end_to_end(jit_always):
    upstream = _fused_process(resident=True)
    downstream = _fused_process()
    payloads = _payloads(8)
    mid = upstream.process_batch("s", payloads)
    assert all(isinstance(p["x"], ResidentArray) for p in mid)
    out = downstream.process_batch("s", mid)
    assert downstream.stats["resident_links"] == 1
    assert len(out) == 8
    # reuse is bit-identical to re-stacking from host
    plain = [{"x": np.array(p["x"])} for p in mid]
    again = _fused_process().process_batch("s", plain)
    assert all(np.array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
               for a, b in zip(out, again))


def test_unlinked_segments_emit_plain_arrays(jit_always):
    proc = _fused_process(resident=False)
    out = proc.process_batch("s", _payloads(4))
    assert not any(isinstance(p["x"], ResidentArray) for p in out)


# ---------------------------------------------------------------------------
# Burst autotune
# ---------------------------------------------------------------------------

def test_autotune_doubles_after_streak(jit_always):
    proc = _fused_process(max_batch=None)
    assert proc.current_max_batch() == fusion.DEFAULT_MAX_BATCH
    full = _payloads(fusion.DEFAULT_MAX_BATCH)
    for _ in range(AUTOTUNE_STREAK):
        proc.process_batch("s", full)
    assert proc.current_max_batch() == 2 * fusion.DEFAULT_MAX_BATCH
    assert proc.stats["max_batch_current"] == 2 * fusion.DEFAULT_MAX_BATCH


def test_autotune_resets_on_partial_burst(jit_always):
    proc = _fused_process(max_batch=None)
    full = _payloads(fusion.DEFAULT_MAX_BATCH)
    for _ in range(AUTOTUNE_STREAK - 1):
        proc.process_batch("s", full)
    proc.process_batch("s", _payloads(2))   # partial: mailbox drained
    for _ in range(AUTOTUNE_STREAK - 1):
        proc.process_batch("s", full)
    assert proc.current_max_batch() == fusion.DEFAULT_MAX_BATCH


def test_autotune_caps_at_max(jit_always):
    proc = _fused_process(max_batch=None)
    cap = fusion.AUTOTUNE_MAX_BATCH
    rounds = 0
    while proc.current_max_batch() < cap and rounds < 100:
        proc.process_batch("s", _payloads(proc.current_max_batch()))
        rounds += 1
    assert proc.current_max_batch() == cap
    for _ in range(2 * AUTOTUNE_STREAK):    # saturated: never exceeds the cap
        proc.process_batch("s", _payloads(cap))
    assert proc.current_max_batch() == cap


def test_autotune_halves_after_sustained_over_budget(jit_always, monkeypatch):
    # a zero budget makes every burst a latency breach: after
    # AUTOTUNE_DOWN_STREAK of them the ceiling halves, and it keeps
    # halving down to the floor of 1 — never below
    monkeypatch.setattr(fusion, "AUTOTUNE_BUDGET_S", 0.0)
    proc = _fused_process(max_batch=None)
    start = proc.current_max_batch()
    full = _payloads(start)
    for _ in range(fusion.AUTOTUNE_DOWN_STREAK):
        proc.process_batch("s", full)
    assert proc.current_max_batch() == start // 2
    assert proc.stats["max_batch_current"] == start // 2
    for _ in range(20 * fusion.AUTOTUNE_DOWN_STREAK):
        proc.process_batch("s", full)
    assert proc.current_max_batch() == 1


def test_autotune_isolated_slow_burst_does_not_shrink(jit_always,
                                                      monkeypatch):
    proc = _fused_process(max_batch=None)
    start = proc.current_max_batch()
    full = _payloads(start)
    # one over-budget burst, then healthy ones: the slow streak resets, so
    # the ceiling never shrinks (and the breach also reset the GROW streak)
    monkeypatch.setattr(fusion, "AUTOTUNE_BUDGET_S", 0.0)
    proc.process_batch("s", full)
    monkeypatch.setattr(fusion, "AUTOTUNE_BUDGET_S", 1e9)
    for _ in range(fusion.AUTOTUNE_DOWN_STREAK):
        proc.process_batch("s", full)
    assert proc.current_max_batch() >= start


def test_declared_max_batch_disables_autotune(jit_always):
    proc = _fused_process(max_batch=8)
    assert not hasattr(proc, "current_max_batch")
    assert proc.default_max_batch == 8
    for _ in range(2 * AUTOTUNE_STREAK):
        proc.process_batch("s", _payloads(8))
    assert proc.stats["max_batch_current"] == 8


# ---------------------------------------------------------------------------
# Metrics surface
# ---------------------------------------------------------------------------

def test_sharded_program_failure_is_retired_and_counted(jit_always,
                                                       monkeypatch, caplog):
    """A failing mesh-sharded program is retired with a count and a warning;
    the burst still runs on the single-device batched program."""
    from repro.kernels import ops

    def failing(stages, mesh, specs=None):
        def program(payload):
            raise RuntimeError("forced sharded-program failure")
        return program

    monkeypatch.setattr(fusion, "fusion_mesh", _mesh1)
    monkeypatch.setattr(ops, "jit_chain_sharded", failing)
    proc = _fused_process(max_batch=8)
    payloads = _payloads(8)
    with caplog.at_level("WARNING", logger="repro.core.fusion"):
        proc.warmup()
        got = proc.process_batch("s", payloads)
    assert proc.stats["sharded_retired"] == 1     # warmup retired it once
    assert proc.stats["sharded_bursts"] == 0
    assert proc.stats["batched_bursts"] == 1      # single-device still ran
    assert proc.stats["device_demotions"] == 0
    assert any("mesh-sharded program failed" in r.getMessage()
               for r in caplog.records)
    want = _fused_process(max_batch=8).process_batch("s", payloads)
    for a, b in zip(got, want):
        assert np.array_equal(a["x"], b["x"])


def test_stats_carry_mesh_fields(jit_always):
    proc = _fused_process()
    for key in ("sharded_bursts", "sharded_retired", "resident_links",
                "mesh_devices", "max_batch_current", "device_demotions"):
        assert key in proc.stats
    assert proc.stats["mesh_devices"] == (fusion.fusion_mesh().size
                                          if fusion.fusion_mesh() else 1)
