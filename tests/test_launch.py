"""Launch-path integration: elastic re-mesh in a subprocess (device-count
changes require fresh jax processes) and the compile cache's placement."""
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
