"""Tier-1 collection config: keep the suite runnable on minimal deps.

The jax-dependent modules (kernels, models, serve/train stack) are skipped
wholesale when jax is not importable — the CI "minimal" matrix leg runs the
platform core (bus/operator/DSL/fusion-fallback) without them.
"""
_NEEDS_JAX = [
    "test_checkpoint.py",
    "test_fault.py",
    "test_kernels.py",
    "test_launch.py",
    "test_mesh.py",
    "test_models.py",
    "test_property.py",
    "test_serve.py",
    "test_sharding.py",
    "test_tpu_compile.py",
    "test_train.py",
]

try:  # a real import (not find_spec): a present-but-broken jax must also skip
    import jax  # noqa: F401
    collect_ignore: list = []
except Exception:
    collect_ignore = list(_NEEDS_JAX)
