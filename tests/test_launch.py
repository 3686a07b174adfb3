"""Launch-path integration: dry-run cell + elastic re-mesh, in subprocesses
(device-count changes require fresh jax processes)."""
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {**os.environ, "PYTHONPATH": "src"}
_CWD = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(prog: str, timeout: int = 560):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(prog)],
                          capture_output=True, text=True, timeout=timeout,
                          env=_ENV, cwd=_CWD)


@pytest.mark.slow
def test_dryrun_cell_on_production_mesh(tmp_path):
    """The flagship deliverable in miniature: one real cell, 512 fake
    devices, lower+compile+roofline — exactly what dryrun --all does."""
    prog = f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_cell
        r = run_cell("mamba2-370m", "decode_32k", multi_pod=False,
                     out_dir={str(tmp_path)!r})
        assert r["ok"] and r["flops_per_device"] > 0
        assert r["wire_bytes_per_device"] >= 0
        assert r["bottleneck"] in ("compute", "memory", "collective")
        r2 = run_cell("mamba2-370m", "decode_32k", multi_pod=True,
                      out_dir={str(tmp_path)!r})
        assert r2["chips"] == 512 and r["chips"] == 256
        print("OK", r["bottleneck"], r2["chips"])
    """
    res = _run(prog)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


@pytest.mark.slow
def test_elastic_shrink_mesh_resumes_training(tmp_path):
    """Node-loss drill: train on a (4,1) mesh, checkpoint, 'lose' two
    devices, rebuild a (2,1) mesh, restore, keep training — losses finite
    and state identical across the re-shard."""
    prog = f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.configs.base import RunConfig
        from repro import models
        from repro.train import optimizer as opt, steps
        from repro.train.checkpoint import CheckpointManager
        from repro.train.fault import ElasticController
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("qwen3-14b")
        run = RunConfig(attention_impl="chunked", attention_chunk=16,
                        remat="none", learning_rate=1e-3, warmup_steps=1)
        key = jax.random.PRNGKey(0)
        batch = {{"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}}
        bshape = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)

        # phase 1: 4-device mesh
        mesh4 = make_host_mesh(4, 1)
        f4, _ = steps.jit_train_step(cfg, run, mesh4, bshape)
        params = models.init(key, cfg)
        state = opt.init_opt_state(params, run)
        params, state, m1 = f4(params, state, batch)
        mgr = CheckpointManager({str(tmp_path)!r})
        mgr.save(1, {{"params": params, "opt": state}}, blocking=True)

        # phase 2: two devices "lost" -> (2,1) mesh, restore, continue
        ec = ElasticController(cfg, run)
        mesh2 = ec.build_mesh(jax.devices()[:2], model_axis=1)
        like = {{"params": jax.eval_shape(lambda: params),
                "opt": jax.eval_shape(lambda: state)}}
        restored, manifest = mgr.restore(like)
        assert manifest["step"] == 1
        f2, _ = steps.jit_train_step(cfg, run, mesh2, bshape)
        p2, s2, m2 = f2(restored["params"], restored["opt"], batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1 + 1.0
        print("OK", l1, l2)
    """
    res = _run(prog)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


@pytest.mark.slow
def test_hlo_collective_parse_multi_device():
    """Sharded matmul on a (1,4) mesh must surface an all-reduce whose wire
    bytes match the ring model 2(n-1)/n * bytes."""
    prog = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.roofline.hlo_cost import analyze_hlo
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(1, 4)
        def f(x, w):
            return x @ w
        xs = NamedSharding(mesh, P(None, "model"))
        ws = NamedSharding(mesh, P("model", None))
        c = jax.jit(f, in_shardings=(xs, ws),
                    out_shardings=NamedSharding(mesh, P())).lower(
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
        t = analyze_hlo(c.as_text())
        expect = 2 * (4 - 1) / 4 * 256 * 256 * 4
        assert t.collective_bytes.get("all-reduce", 0) == expect, t.collective_bytes
        print("OK", t.collective_bytes)
    """
    res = _run(prog)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


def test_compile_cache_placed_from_outside_or_fixed_in_checkout(
        monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without it
    the cache is the fixed checkout path (the path keys the cache)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == os.path.join(_CWD, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path      # same path every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
