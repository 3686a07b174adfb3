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
import re

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
    # mellum2-12b-a2.5b's experts: a decode step's 512 routed rows, and a
    # 6,144-token prefill's, against layer 3 of an 8-layer stack
    "expert_gmm_decode": (
        lambda x, w, n: ops.expert_gmm(x, w, n, jnp.int32(3),
                                       interpret=False),
        [(512, 2304), (8, 64, 2304, 896), ((64,), jnp.int32)]),
    "expert_gmm_prefill": (
        lambda x, w, n: ops.expert_gmm(x, w, n, jnp.int32(3),
                                       interpret=False),
        [(49152, 896), (8, 64, 896, 2304), ((64,), jnp.int32)]),
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


def _top_level_ops(hlo: str):
    """(op, result type) of every instruction outside a fusion's body, a
    fusion named by the op at its root."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line.strip())
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line
             for c in re.findall(r"calls=%?([\w.\-]+)", line)}

    def op_of(rhs):
        m = re.search(r"\}? ([\w\-]+)\((%[\w.\-]+)?", rhs)
        return (m.group(1), m.group(2)) if m else ("", None)

    def root_op(comp):
        """The root's op, looking through bitcasts to what they view."""
        defs = {}
        for line in comps[comp]:
            lhs, _, rhs = line.partition(" = ")
            defs[lhs.split()[-1]] = op_of(rhs)
            if lhs.startswith("ROOT"):
                root = lhs.split()[-1]
        op, arg = defs[root]
        while op == "bitcast" and arg in defs:
            op, arg = defs[arg]
        return op

    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            rhs = line.partition(" = ")[2]
            op = op_of(rhs)[0]
            if op == "fusion":
                op = root_op(re.search(r"calls=%?([\w.\-]+)", rhs).group(1))
            yield op, rhs.split("{", 1)[0]


def test_dense_decode_step_reads_and_writes_cache_in_place(one_chip):
    """qwen3-14b's decode step at its widths (2 layers, 32 slots, 2,048
    positions, bf16 cache donated) copies no cache: no top-level copy,
    dynamic-slice or dynamic-update-slice produces the stacked cache or one
    layer of it.  Only the new token's rows are scattered in place, and the
    Pallas kernel reads each layer where it lies."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro import models
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.distributed.act_sharding import activation_mesh

    cfg = dataclasses.replace(get_config("qwen3-14b"), n_layers=2)
    run = RunConfig(attention_impl="chunked", attention_chunk=256,
                    remat="none")
    B, S = 32, 2048
    mesh = Mesh(np.array(one_chip._device_assignment).reshape(1, 1),
                ("data", "model"))

    def step(params, cache, batch):
        with activation_mesh(mesh):
            logits, cache = models.decode_step(params, cache, batch, cfg, run)
        return jnp.argmax(logits, axis=-1), cache

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = specs(jax.eval_shape(lambda: models.init(jax.random.PRNGKey(0),
                                                      cfg)))
    cache = specs(jax.eval_shape(lambda: models.init_cache(cfg, B, S)))
    batch = {"tokens": _spec(one_chip, (B, 1), jnp.int32),
             "seq_lens": _spec(one_chip, (B,), jnp.int32),
             "active": _spec(one_chip, (B,), jnp.bool_)}
    hlo = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, batch).compile().as_text()
    whole = f"bf16[{cfg.n_layers},{B},{S},8,128]"
    layer = (f"bf16[{B},{S},8,128]", f"bf16[1,{B},{S},8,128]")
    copies = [(op, ty) for op, ty in _top_level_ops(hlo)
              if op in ("copy", "dynamic-slice", "dynamic-update-slice")
              and (ty == whole or ty in layer)]
    assert not copies, copies
    assert "tpu_custom_call" in hlo


def test_swa_moe_decode_step_reads_weights_and_caches_in_place(one_chip):
    """mellum2-12b-a2.5b's decode step at its widths (two periods: 6
    sliding and 2 full layers, 64 slots, 8,192 positions, bf16 caches
    donated) copies neither cache nor any expert weights: no top-level
    copy, dynamic-slice or dynamic-update-slice produces the full cache,
    the window rings, one layer of either, or a layer's (or a period's)
    expert matrices.  The grouped-matmul kernel reads each layer of the
    stacked experts where it lies, under its own name."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from repro import models
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.distributed.act_sharding import activation_mesh

    cfg = dataclasses.replace(get_config("mellum2-12b-a2.5b"), n_layers=8)
    run = RunConfig(attention_impl="chunked", attention_chunk=256,
                    remat="none")
    B, S = 64, 8192
    mesh = Mesh(np.array(one_chip._device_assignment).reshape(1, 1),
                ("data", "model"))

    def step(params, cache, batch):
        with activation_mesh(mesh):
            logits, cache, counts = models.decode_step(
                params, cache, batch, cfg, run, counters=True)
        return jnp.concatenate([jnp.argmax(logits, axis=-1), counts]), cache

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = specs(jax.eval_shape(lambda: models.init(jax.random.PRNGKey(0),
                                                      cfg)))
    cache = specs(jax.eval_shape(lambda: models.init_cache(cfg, B, S)))
    batch = {"tokens": _spec(one_chip, (B, 1), jnp.int32),
             "seq_lens": _spec(one_chip, (B,), jnp.int32),
             "active": _spec(one_chip, (B,), jnp.bool_)}
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, batch).compile()
    hlo = compiled.as_text()
    big = {f"bf16[2,{B},{S},4,128]", f"bf16[{B},{S},4,128]",
           f"bf16[1,{B},{S},4,128]", f"bf16[6,{B},1024,4,128]",
           f"bf16[{B},1024,4,128]", f"bf16[1,{B},1024,4,128]",
           "bf16[64,2304,896]", "bf16[64,896,2304]",
           "bf16[4,64,2304,896]", "bf16[4,64,896,2304]",
           "bf16[1,64,2304,896]", "bf16[1,64,896,2304]"}
    copies = [(op, ty) for op, ty in _top_level_ops(hlo)
              if op in ("copy", "dynamic-slice", "dynamic-update-slice")
              and ty in big]
    assert not copies, copies
    kernels = [line.split(" = ")[0].strip() for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # three grouped matmuls per layer of the unrolled period, named for
    # the trace; one decode-attention read per layer
    assert sum("expert_gmm" in k for k in kernels) == 3 * 4, kernels
    # temporaries stay small: a copy of a period's experts would be 3.2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
