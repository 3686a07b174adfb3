"""The names the benchmark's trace readers match the compiled programs by
(``bench/readers.py``): a refactor that renames one would turn an accepted
device-trace metric to null without an error, so each is pinned here on the
lowered module."""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

CHAIN = [("map", lambda p: {"x": p["x"] * 2.0}),
         ("filter", lambda p: p["x"].max() > 1.0)]


@pytest.fixture(scope="module")
def engine():
    from repro import models
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.serve import ServeEngine

    cfg = get_smoke_config("qwen3-32b")
    params = models.init(jax.random.PRNGKey(0), cfg)
    run = RunConfig(attention_impl="naive", remat="none",
                    attention_chunk=16)
    return ServeEngine(cfg, run, params, n_slots=2, max_seq=64)


def _fused_single(engine):
    from repro.kernels.ops import jit_chain
    return jit_chain(CHAIN).lower({"x": jnp.zeros((4, 8))})


def _fused_batched(engine):
    from repro.kernels.ops import jit_chain_batched
    return jit_chain_batched(CHAIN).lower({"x": jnp.zeros((2, 4, 8))})


def _decode(engine):
    n = engine.n_slots
    batch = {"tokens": jnp.zeros((n, 1), jnp.int32),
             "seq_lens": jnp.zeros((n,), jnp.int32),
             "active": jnp.ones((n,), bool)}
    return engine._decode.lower(engine.params, engine.cache, batch)


def _prefill(engine):
    batch = {"tokens": jnp.zeros((1, 32), jnp.int32),
             "last_index": jnp.asarray([3], jnp.int32)}
    return engine._get_prefill(32).lower(engine.params, batch)


def _insert(which):
    def lower(engine):
        fn = engine._insert_fns[which]
        pool = jnp.zeros((2, engine.n_slots, 8, 4))
        piece = jnp.zeros((2, 1, 8, 4))
        return fn.lower(pool, piece, 1, 3)
    return lower


@pytest.mark.parametrize("name,lower", [
    ("jit_program", _fused_single),
    ("jit_single", _fused_batched),
    ("jit_step", _decode),
    ("jit_prefill", _prefill),
    ("jit_insert_kv", _insert(0)),
    ("jit_insert_state", _insert(1)),
])
def test_reader_program_names(engine, name, lower):
    text = lower(engine).as_text()
    assert f"module @{name} " in text, text.splitlines()[0]


def test_readers_list_the_pinned_names():
    import pathlib
    import sys
    bench = pathlib.Path(__file__).resolve().parent.parent / "bench"
    sys.path.insert(0, str(bench))
    try:
        import readers
    finally:
        sys.path.remove(str(bench))
    pinned = {"jit_program", "jit_single", "jit_step", "jit_prefill",
              "jit_insert_kv", "jit_insert_state"}
    listed = set(readers.FUSED_PROGRAMS + readers.DECODE_PROGRAMS
                 + readers.PREFILL_PROGRAMS)
    assert pinned <= listed
