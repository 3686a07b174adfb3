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


# -- the sliding-window / routed-expert family (mellum2-12b-a2.5b) ----------

@pytest.fixture(scope="module")
def swa_moe_engine():
    from repro import models
    from repro.configs import get_smoke_config
    from repro.configs.base import RunConfig
    from repro.serve import ServeEngine

    cfg = get_smoke_config("mellum2-12b-a2.5b")
    params = models.init(jax.random.PRNGKey(0), cfg)
    run = RunConfig(attention_impl="chunked", remat="none",
                    attention_chunk=16)
    return ServeEngine(cfg, run, params, n_slots=2, max_seq=64)


def _ring_insert(engine):
    fn = engine._insert_fns[0]
    pool = engine.cache["k_win"]
    piece = jnp.zeros((pool.shape[0], 1) + pool.shape[2:], pool.dtype)
    return fn.lower(pool, piece, 1, 3)


@pytest.mark.parametrize("name,lower", [
    ("jit_step", _decode),
    ("jit_prefill", _prefill),
    ("jit_insert_kv", _ring_insert),
])
def test_swa_moe_program_names(swa_moe_engine, name, lower):
    text = lower(swa_moe_engine).as_text()
    assert f"module @{name} " in text, text.splitlines()[0]


def test_expert_kernel_name():
    """The grouped-matmul kernel runs under a jit named ``expert_gmm``,
    whose name the compiled program gives its custom calls (the TPU compile
    is pinned in test_tpu_compile.py); the reader matches that name."""
    import pathlib
    import sys

    from repro.kernels import ops
    lhs = jnp.zeros((128, 128), jnp.bfloat16)
    rhs = jnp.zeros((2, 4, 128, 128), jnp.bfloat16)
    sizes = jnp.asarray([32, 32, 0, 64], jnp.int32)
    text = jax.jit(lambda a, b, c: ops.expert_gmm(
        a, b, c, jnp.int32(1), interpret=True)).lower(lhs, rhs,
                                                     sizes).as_text()
    assert "call @expert_gmm(" in text
    bench = pathlib.Path(__file__).resolve().parent.parent / "bench"
    sys.path.insert(0, str(bench))
    try:
        import readers_swa_moe
    finally:
        sys.path.remove(str(bench))
    assert readers_swa_moe.EXPERT_KERNEL == "expert_gmm"


def test_engine_spans_on_the_swa_moe_family(swa_moe_engine, tmp_path):
    """A tick of the new family opens every datax.engine.* span on the
    profiler's host plane, as the dense family's does."""
    from jax.profiler import ProfileData

    eng = swa_moe_engine
    eng.submit("warm", [3, 4, 5], max_new_tokens=3)
    eng.run_until_idle()            # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit("traced", [1, 2, 3, 4, 5, 6], max_new_tokens=3)
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    names = set()
    for plane in ProfileData.from_file(
            str(next(tmp_path.rglob("*.xplane.pb")))).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    for span in ("tick", "plan", "prefill", "prefill_wait", "insert",
                 "decode", "decode_wait"):
        assert f"datax.engine.{span}" in names, span
    assert eng.metrics["moe_routed_pairs"] > 0
