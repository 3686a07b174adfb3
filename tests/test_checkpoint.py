"""Checkpointing: atomic commit, checksums, retention, elastic restore."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import CheckpointError, CheckpointManager


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 16)),
                       "b": jnp.zeros((16,), jnp.bfloat16)},
            "opt": {"m": jnp.ones((8, 16)), "count": jnp.int32(5)}}


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = _state()
    mgr.save(3, state, blocking=True)
    restored, manifest = mgr.restore(jax.eval_shape(lambda: state))
    assert manifest["step"] == 3
    np.testing.assert_array_equal(restored["params"]["w"],
                                  state["params"]["w"])
    assert restored["params"]["b"].dtype == jnp.bfloat16
    assert int(restored["opt"]["count"]) == 5


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    mgr.wait()
    assert mgr.latest_step() == 1


def test_atomicity_torn_write_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(), blocking=True)
    # simulate a crash mid-write of step 2: tmp dir exists, no manifest
    torn = tmp_path / "step_00000002.tmp"
    torn.mkdir()
    (torn / "shard_00000.dxckpt").write_bytes(b"partial garbage")
    assert mgr.latest_step() == 1  # torn write invisible
    restored, manifest = mgr.restore(jax.eval_shape(lambda: _state()))
    assert manifest["step"] == 1


def test_checksum_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(), blocking=True)
    shard = tmp_path / "step_00000001" / "shard_00000.dxckpt"
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    with pytest.raises(CheckpointError):
        mgr.restore(jax.eval_shape(lambda: _state()))


def test_retention_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, _state(), blocking=True)
    assert mgr.all_steps() == [3, 4]


def test_elastic_restore_new_sharding(tmp_path):
    """Restore re-lays-out onto a different (here trivial) mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    state = _state()
    mgr.save(1, state, blocking=True)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), state)
    restored, _ = mgr.restore(jax.eval_shape(lambda: state),
                              shardings=shardings)
    np.testing.assert_array_equal(restored["params"]["w"],
                                  state["params"]["w"])
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())
