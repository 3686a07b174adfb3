"""Compile for a described TPU v5e — no chip needed, nothing runs.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached.  It refuses what interpret mode accepts:
blocks whose last two dims miss the (8, 128) tiling, kernels that need more
fast memory than they may use, programs that do not fit the device.  So the
main path's kernels compile here at real widths (qwen3-14b attention,
mamba2-370m SSD), and so does the fused batched device chain at camera-frame
size.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels import ops  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# name -> (kernel, argument shapes); widths of the configs that select them
_KERNELS = {
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        [(1, 2048, 40, 128), (1, 2048, 8, 128), (1, 2048, 8, 128)]),
    "decode_attention": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
        [(8, 40, 128), (8, 2048, 8, 128), (8, 2048, 8, 128),
         ((8,), jnp.int32)]),
    # head_dim 64 with several kv heads (whisper-large-v3): lane-padded
    "decode_attention_hd64": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, interpret=False),
        [(8, 20, 64), (8, 2048, 20, 64), (8, 2048, 20, 64),
         ((8,), jnp.int32)]),
    "ssd_scan": (
        lambda x, dt, a, b, c: ops.ssd_scan(x, dt, a, b, c, chunk=256,
                                            interpret=False),
        [(1, 2048, 32, 64), ((1, 2048, 32), jnp.float32),
         ((32,), jnp.float32), (1, 2048, 1, 128), (1, 2048, 1, 128)]),
    "rmsnorm": (lambda x, w: ops.rmsnorm(x, w, interpret=False),
                [(2048, 5120), (5120,)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes = _KERNELS[name]
    args = [_spec(one_chip, *s) if isinstance(s[0], tuple)
            else _spec(one_chip, s) for s in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel


def test_fused_batched_chain_compiles_for_v5e(one_chip):
    """The vmapped program a fused camera chain runs per 32-frame burst."""
    stages = [("map", lambda p: {"x": p["x"] * 2.0}),
              ("filter", lambda p: p["x"].max() > 9.0),
              ("map", lambda p: {"x": p["x"] - 1.0}),
              ("map", lambda p: {"x": p["x"].clip(0.0, 6.0)})]
    program = ops.jit_chain_batched(stages)
    burst = {"x": _spec(one_chip, (32, 480, 640), jnp.float32)}
    compiled = program.lower(burst).compile()
    out, keep = compiled.out_info
    assert out["x"].shape == (32, 480, 640) and keep.shape == (32,)
