"""Sharding rules: divisibility safety, ZeRO specs, batch specs, roofline
parsing — plus a multi-device GSPMD equivalence test in a subprocess."""
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.base import TRAIN_4K
from repro.distributed import sharding as shard
from repro.launch.presets import run_preset
from repro.train import steps


class FakeMesh:
    """Shape-only stand-in (rules never touch devices)."""

    def __init__(self, shape):
        self.shape = dict(shape)

    @property
    def devices(self):
        raise AssertionError("rules must not touch mesh devices")


MESH = FakeMesh({"data": 16, "model": 16})


def _axis_sizes(spec, shape, mesh):
    for entry, dim in zip(tuple(spec) + (None,) * (len(shape) - len(spec)),
                          shape):
        axes = entry if isinstance(entry, tuple) else \
            (entry,) if entry else ()
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        yield dim, n


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divisible(arch):
    """Every sharded dim must be divisible by its axis product — indivisible
    dims must be left unsharded (whisper's 20 heads etc.)."""
    cfg = get_config(arch)
    run = run_preset(cfg, TRAIN_4K)
    params_shape = steps.abstract_params(cfg)
    specs = shard.param_specs(params_shape, cfg, run, MESH)
    leaves = jax.tree.leaves(params_shape)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        for dim, n in _axis_sizes(spec, leaf.shape, MESH):
            assert dim % n == 0, (arch, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["qwen3-32b", "grok-1-314b", "mamba2-370m"])
def test_opt_specs_zero1(arch):
    """m/v must be sharded at least as much as params (ZeRO-1 adds 'data')."""
    cfg = get_config(arch)
    run = run_preset(cfg, TRAIN_4K)
    params_shape, opt_shape, pspecs, ospecs = steps.train_shardings(
        cfg, run, MESH)
    m_specs = jax.tree.leaves(ospecs["m"], is_leaf=lambda s: isinstance(s, P))
    p_specs = jax.tree.leaves(pspecs, is_leaf=lambda s: isinstance(s, P))
    p_leaves = jax.tree.leaves(params_shape)
    for pl, ps, ms in zip(p_leaves, p_specs, m_specs):
        def n_shards(spec):
            total = 1
            for _, n in _axis_sizes(spec, pl.shape, MESH):
                total *= n
            return total
        assert n_shards(ms) >= n_shards(ps), (arch, pl.shape, ps, ms)
        for dim, n in _axis_sizes(ms, pl.shape, MESH):
            assert dim % n == 0


def test_whisper_heads_not_tensor_sharded():
    cfg = get_config("whisper-large-v3")  # 20 heads % 16 != 0
    run = run_preset(cfg, TRAIN_4K)
    params_shape = steps.abstract_params(cfg)
    specs = shard.param_specs(params_shape, cfg, run, MESH)
    wq_spec = specs["decoder"]["attn"]["wq"]
    assert "model" not in jax.tree.leaves(
        [list(wq_spec)], is_leaf=lambda x: True) or \
        wq_spec[-1] != "model"
    # but its MLP IS tensor-parallel (5120 % 16 == 0)
    assert specs["decoder"]["mlp"]["w_up"][-1] == "model"


def test_batch_spec_for():
    assert shard.batch_spec_for(MESH, 256, 1) == P(("data",), None)
    assert shard.batch_spec_for(MESH, 1, 1) == P(None, None)  # indivisible
    pod_mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert shard.batch_spec_for(pod_mesh, 256, 0) == P(("pod", "data"))
    assert shard.batch_spec_for(pod_mesh, 16, 0) == P(("pod",))  # partial


def test_hlo_cost_walker_known_case():
    """Loop-aware flops: a 10-step scanned matmul == its unrolled form."""
    import jax.numpy as jnp
    from repro.roofline.hlo_cost import analyze_hlo

    def scanned(x, w):
        def b(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(b, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    t = analyze_hlo(jax.jit(scanned).lower(x, x).compile().as_text())
    assert abs(t.flops - 10 * 2 * 256 ** 3) / (10 * 2 * 256 ** 3) < 0.01


@pytest.mark.slow
def test_multi_device_train_step_matches_single(tmp_path):
    """GSPMD equivalence: the sharded (2,2)-mesh train step computes the
    same loss as single-device — run in a subprocess with 4 host devices."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.configs.base import RunConfig
        from repro import models
        from repro.train import optimizer as opt, steps
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("qwen3-14b")
        run = RunConfig(attention_impl="chunked", attention_chunk=16,
                        remat="full", microbatches=2)
        key = jax.random.PRNGKey(0)
        params = models.init(key, cfg)
        opt_state = opt.init_opt_state(params, run)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}

        # single device
        f1 = jax.jit(steps.make_train_step(cfg, run))
        _, _, m1 = f1(params, opt_state, batch)

        # (2,2) mesh via the framework's sharding derivation
        mesh = make_host_mesh(2, 2)
        bshape = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)
        f2, _ = steps.jit_train_step(cfg, run, mesh, bshape)
        _, _, m2 = f2(params, opt_state, batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / max(abs(l1), 1e-9) < 2e-2, (l1, l2)
        print("OK", l1, l2)
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=560,
                         env={**__import__('os').environ,
                              "PYTHONPATH": "src"},
                         cwd=__import__('os').path.dirname(
                             __import__('os').path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout
