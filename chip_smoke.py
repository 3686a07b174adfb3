"""Chip smoke test: DataX's main path on a TPU, through its own entry points.

    python chip_smoke.py             # one chip: serve, fused chain, kernels
    python chip_smoke.py --chips 4   # fused chain on a 4-chip data mesh only

Every phase runs in this one process (a chip belongs to one process):

* ``serve`` — ``examples/serve_lm.py`` deployed through ``connect()`` with
  qwen3-14b at its published widths, depth cut to 8 layers, bf16 random
  weights from ``--seed``: 12 requests over 3 sessions, prompts of 100-500
  tokens, 32 new tokens each, 8 KV slots of 2,048 positions.  Before that,
  the same engine's prefill logits and one decode step through its KV cache
  are checked against ``models.forward`` over the same tokens.
* ``fused`` — a camera chain of three ``.map(device=True)`` stages and one
  ``.filter(device=True)`` over 480x640 float32 frames under
  ``.scaled(max_batch=32)``: 512 seeded frames through fusion and the
  executor, every output checked against a plain numpy chain.
* ``kernels`` — the four Pallas kernels compiled for the chip at qwen3-14b
  and mamba2-370m widths, checked against ``kernels/ref.py``.

``--chips 4`` runs only the fused chain, mesh-sharded over four chips, and
compares it bit for bit with the single-device batched program.

Each phase prints a JSON summary line; times in them are set-up times
(compile included), not performance figures.  The last line is
``{"ok": true, "device": {...}}``.  Anything but a TPU, or any failed
phase, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

SERVE_ARCH = "qwen3-14b"
SERVE_LAYERS = 8            # of 40: the weights must fit one 16 GB chip
SERVE_SLOTS = 8
SERVE_MAX_SEQ = 2048
SERVE_REQUESTS = 12
SERVE_PROMPT = (100, 501)   # [min, max): prefill buckets 128, 256, 512
SERVE_NEW_TOKENS = 32
CHECK_PROMPT_LEN = 300

FRAME_SHAPE = (480, 640)
FRAMES = 512
FRAMES_AHEAD = 128          # the camera never runs further ahead of the
                            # fused unit: its backlog stays below the
                            # unit's 256-slot drop-oldest mailbox
BRIGHT = 9.0                # filter threshold on a frame's max after gain


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _weights_gb(params) -> float:
    import jax
    return sum(a.nbytes for a in jax.tree.leaves(params)) / 1e9


def check_engine_logits(model: dict, seed: int) -> dict:
    """The engine's prefill logits and one decode step through its KV cache
    against ``models.forward`` over the same tokens, on the chip."""
    import functools

    import jax
    import numpy as np
    from serve_lm import make_model

    from repro import models
    from repro.serve import ServeEngine

    cfg, run, params = make_model(model["arch"], model["layers"],
                                  model["attention"], seed)
    engine = ServeEngine(cfg, run, params, n_slots=SERVE_SLOTS,
                         max_seq=SERVE_MAX_SEQ)
    rng = np.random.default_rng(seed + 1)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab, CHECK_PROMPT_LEN)]
    engine.submit("check", prompt, max_new_tokens=2)
    engine.tick()                                    # admits: prefill only
    req = engine.batcher.live["check"]
    prefill = np.asarray(req.prefill_logits, np.float32)
    first = req.generated[0]
    active = np.zeros((SERVE_SLOTS,), bool)
    active[req.slot] = True
    decode = jax.jit(functools.partial(models.decode_step, cfg=cfg, run=run))
    dec_logits, _ = decode(params, engine.cache, {
        "tokens": engine.last_token[:, None], "seq_lens": engine.seq_lens,
        "active": active})
    dec_logits = np.asarray(dec_logits, np.float32)
    forward = jax.jit(functools.partial(models.forward, cfg=cfg, run=run))
    ref, _ = forward(params, {"tokens": np.asarray([prompt + [first]],
                                                    np.int32)})
    ref = np.asarray(ref, np.float32)[0]
    # Tolerance: both sides run the same bf16 weights and activations and
    # differ only in layout and reduction order (the engine pads the prompt
    # to its bucket and reads K/V back from its cache; the reference
    # attends over the exact tokens).  Each bf16 rounding moves a value by
    # up to 2^-9 relative, and some fifty roundings on the way through 8
    # layers compound to percent-level noise (1-2% relative L2 at small
    # width on the CPU; float32 weights give 2e-6 there, so the path itself
    # is exact).  A wrong cache slot, position or mask errs by order one.
    # So: relative L2 error <= 5e-2, largest error <= 1e-1 of max |logit|.
    out = {"weights_gb": _weights_gb(params), "prompt_len": len(prompt)}
    ok = True
    n = len(prompt)
    for name, got, want in (("prefill", prefill, ref[n - 1]),
                            ("decode", dec_logits[req.slot], ref[n])):
        rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        max_err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        passed = rel_l2 <= 5e-2 and max_err <= 1e-1 * scale
        ok = ok and passed
        out[name] = {"rel_l2": rel_l2, "max_abs_err": max_err,
                     "max_abs_ref": scale, "within_tolerance": passed}
    finite = bool(np.isfinite(prefill).all() and np.isfinite(dec_logits).all()
                  and np.isfinite(ref).all())
    out["all_logits_finite"] = finite
    out["tolerance"] = "rel_l2<=5e-2 and max_abs_err<=1e-1*max_abs_ref"
    if not (ok and finite):
        raise AssertionError(f"engine logits disagree with models.forward: "
                             f"{out}")
    return out


def serve_phase(seed: int) -> None:
    import serve_lm

    from repro.configs import get_config
    from repro.core import connect, drain

    t0 = time.perf_counter()
    full = get_config(SERVE_ARCH)
    model = {"arch": SERVE_ARCH, "layers": SERVE_LAYERS,
             "max_seq": SERVE_MAX_SEQ, "attention": "chunked", "seed": seed}
    check = check_engine_logits(model, seed)
    gc.collect()                 # one copy of the weights on the chip at once
    t_check = time.perf_counter() - t0

    app = serve_lm.build_app(requests=SERVE_REQUESTS, slots=SERVE_SLOTS,
                             max_new=SERVE_NEW_TOKENS, prompt=SERVE_PROMPT,
                             **model)
    with connect() as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("responses", maxsize=SERVE_REQUESTS + 8)
        op.start_pending_sensors()
        done = [m.payload for m in drain(sub, SERVE_REQUESTS, timeout=900)]
    ids = sorted(p["request_id"] for p in done)
    tokens = [p["tokens"] for p in done]
    plens = [p["prompt_len"] for p in done]
    if ids != [f"req-{i:03d}" for i in range(SERVE_REQUESTS)]:
        raise AssertionError(f"requests answered: {ids}")
    if any(t != SERVE_NEW_TOKENS for t in tokens):
        raise AssertionError(f"token counts {tokens}, want "
                             f"{SERVE_NEW_TOKENS} each")
    if not all(SERVE_PROMPT[0] <= n < SERVE_PROMPT[1] for n in plens):
        raise AssertionError(f"prompt lengths {plens}")
    log("serve", model=SERVE_ARCH, d_model=full.d_model,
        heads=[full.n_heads, full.n_kv_heads], head_dim=full.head_dim,
        d_ff=full.d_ff, vocab=full.vocab, dtype=full.param_dtype,
        layers=SERVE_LAYERS,
        reduced={"n_layers": f"{full.n_layers} -> {SERVE_LAYERS}"},
        kv_cache={"slots": SERVE_SLOTS, "max_seq": SERVE_MAX_SEQ},
        requests=SERVE_REQUESTS,
        sessions=len({p["session"] for p in done}),
        answered=len(done), tokens_each=sorted(set(tokens)),
        prompt_lens=sorted(plens), logits_check=check,
        setup_s={"logits_check": t_check,
                 "deploy_and_serve": time.perf_counter() - t0 - t_check})


# ---------------------------------------------------------------------------
# fused
# ---------------------------------------------------------------------------

def _frames(seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    frames = rng.standard_normal((FRAMES,) + FRAME_SHAPE, dtype=np.float32)
    # per-frame exposure, so the brightness filter keeps some and drops some
    frames *= rng.uniform(0.5, 1.5, (FRAMES, 1, 1)).astype(np.float32)
    return frames


def _numpy_chain(frames):
    """The reference: the same four stages in plain numpy.  Every stage is
    exact or a single rounding in float32, so the device chain must match
    bit for bit."""
    import numpy as np
    out = []
    for f in frames:
        g = f * np.float32(2.0)
        if g.max() > np.float32(BRIGHT):
            out.append(np.clip(g - np.float32(1.0), np.float32(0.0),
                               np.float32(6.0)))
    return out


def run_fused_chain(frames) -> tuple[list, dict]:
    """Deploy the camera chain through App -> connect() -> fusion ->
    executor; returns (outputs in order, the fused unit's sidecar metrics)."""
    from repro.core import App, StreamSchema, connect

    frame = StreamSchema.device(x=(FRAME_SHAPE, "float32"))
    app = App("chip-smoke-camera")
    unit = {}  # the fused unit's sidecar, once deployed

    @app.driver(emits=frame)
    def camera(ctx, ahead=FRAMES_AHEAD):
        def gen():
            for i in range(len(frames)):
                while ctx.running and i - unit["sc"].batch_msgs >= ahead:
                    time.sleep(0.002)
                if not ctx.running:
                    return
                yield {"x": frames[i]}
        return gen()

    (app.sense("frames", camera)
        .map(lambda p: {"x": p["x"] * 2.0}, emits=frame, device=True,
             name="gain")
        .filter(lambda p: p["x"].max() > BRIGHT, device=True, name="bright")
        .map(lambda p: {"x": p["x"] - 1.0}, emits=frame, device=True,
             name="black_level")
        .map(lambda p: {"x": p["x"].clip(0.0, 6.0)}, emits=frame,
             device=True, name="clip")
        .scaled(max_batch=32)
        .tap())                  # promised to the subscriber below
    with connect(start=False) as op:
        app.deploy(op, start_sensors=False)
        sub = op.subscribe("clip", maxsize=2 * FRAMES)
        unit["sc"] = op.executor.instances_of("clip")[0].sidecar
        op.start_pending_sensors()
        deadline = time.monotonic() + 600
        while True:
            m = unit["sc"].metrics()
            if m["batch_msgs"] + m["dropped"] >= len(frames):
                break
            if time.monotonic() > deadline:
                raise TimeoutError(f"fused unit took {m['batch_msgs']} of "
                                   f"{len(frames)} frames")
            time.sleep(0.05)
        out = []
        while True:
            msg = sub.next(timeout=1.0)
            if msg is None:
                break
            out.append(msg.payload["x"])
        return out, unit["sc"].metrics()


def fused_phase(seed: int, devices: int) -> None:
    import numpy as np

    t0 = time.perf_counter()
    frames = _frames(seed)
    want = _numpy_chain(frames)
    t_data = time.perf_counter() - t0
    runs = {}
    # one chip: the single-device batched program.  Four chips: the
    # mesh-sharded program, then the single-device one on the same frames.
    modes = ["single"] if devices == 1 else ["sharded", "single"]
    for mode in modes:
        t1 = time.perf_counter()
        if mode == "single":
            os.environ["DATAX_FUSION_MESH"] = "0"
        got, m = run_fused_chain(frames)
        kept, filtered = len(got), len(frames) - len(want)
        checks = {
            "frames_accounted": m["batch_msgs"] == len(frames)
            and m["dropped"] == 0 and kept + filtered == len(frames),
            "matches_numpy": kept == len(want)
            and all(np.array_equal(a, b) for a, b in zip(got, want)),
            "batched": m["batched_bursts"] > 0,
            "on_device": m["device_fallbacks"] == 0
            and m["device_demotions"] == 0 and m["unstackable_bursts"] == 0,
        }
        if mode == "sharded":
            checks["sharded"] = (m["sharded_bursts"] > 0
                                 and m["mesh_devices"] == devices
                                 and m["sharded_retired"] == 0)
        counters = {k: m[k] for k in (
            "batch_msgs", "dropped", "batches", "max_batch_seen",
            "batched_bursts", "batched_msgs", "sharded_bursts",
            "sharded_retired", "mesh_devices", "device_fallbacks",
            "device_demotions", "unstackable_bursts")}
        runs[mode] = (got, checks, counters, time.perf_counter() - t1)
    fields = {}
    if "sharded" in runs:
        a, b = runs["sharded"][0], runs["single"][0]
        fields["sharded_bit_identical_to_single"] = (
            len(a) == len(b) and all(np.array_equal(x, y)
                                     for x, y in zip(a, b)))
    log("fused", frame=list(FRAME_SHAPE), dtype="float32", frames=FRAMES,
        stages=["map gain", "filter bright", "map black_level", "map clip"],
        max_batch=32, kept=len(want), filtered=FRAMES - len(want),
        runs={k: {"checks": v[1], "counters": v[2], "setup_s": v[3]}
              for k, v in runs.items()},
        setup_s={"frames_and_numpy_reference": t_data}, **fields)
    failed = [f"{k}.{c}" for k, v in runs.items()
              for c, passed in v[1].items() if not passed]
    if fields.get("sharded_bit_identical_to_single") is False:
        failed.append("sharded_bit_identical_to_single")
    if failed:
        raise AssertionError(f"fused chain checks failed: {failed}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    q = rand((1, 2048, 40, 128))
    k, v = rand((1, 2048, 8, 128)), rand((1, 2048, 8, 128))
    dq = rand((8, 40, 128))
    kc, vc = rand((8, 2048, 8, 128)), rand((8, 2048, 8, 128))
    lens = jax.random.randint(next(keys), (8,), 1, 2049)
    xs = rand((1, 2048, 32, 64))
    dt = jax.nn.softplus(rand((1, 2048, 32), jnp.float32))
    A = -jnp.exp(rand((32,), jnp.float32) * 0.5)
    Bm, Cm = rand((1, 2048, 1, 128)), rand((1, 2048, 1, 128))
    xn, w = rand((2048, 5120)), rand((5120,))

    # bf16 outputs: the kernel and the float32 reference differ by output
    # rounding and accumulation order — a few bf16 steps (2^-8), as in
    # tests/test_kernels.py.  The SSD final state is float32 throughout.
    bf16 = dict(atol=5e-2, rtol=5e-2)
    cases = {
        "flash_attention": (
            lambda: ops.flash_attention(q, k, v, causal=True,
                                        interpret=False),
            lambda: ref.flash_attention_ref(q, k, v, causal=True),
            [bf16]),
        "decode_attention": (
            lambda: ops.decode_attention(dq, kc, vc, lens, interpret=False),
            lambda: ref.decode_attention_ref(dq, kc, vc, lens), [bf16]),
        "ssd_scan": (
            lambda: ops.ssd_scan(xs, dt, A, Bm, Cm, chunk=256,
                                 interpret=False),
            lambda: ref.ssd_scan_ref(xs, dt, A, Bm, Cm),
            [bf16, dict(atol=2e-3, rtol=2e-3)]),
        "rmsnorm": (lambda: ops.rmsnorm(xn, w, interpret=False),
                    lambda: ref.rmsnorm_ref(xn, w), [bf16]),
    }
    report, failed = {}, []
    for name, (kernel, reference, tols) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower().compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name} did not compile to a Mosaic kernel")
        got = jax.tree.leaves(compiled())
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(reference)())
        errs = []
        for g, r, tol in zip(got, want, tols):
            g = np.asarray(g, np.float32)
            r = np.asarray(r, np.float32)
            err = float(np.abs(g - r).max())
            ok = bool(np.isfinite(g).all()
                      and np.allclose(g, r, atol=tol["atol"],
                                      rtol=tol["rtol"]))
            errs.append({"shape": list(g.shape), "max_abs_err": err,
                         "tol": tol, "ok": ok})
            if not ok:
                failed.append(name)
        report[name] = {"outputs": errs,
                        "setup_s": time.perf_counter() - t0}
    log("kernels", compiled_for="tpu (Mosaic, not interpret mode)",
        kernels=report)
    if failed:
        raise AssertionError(f"kernels disagree with kernels/ref.py: "
                             f"{sorted(set(failed))}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the fused chain, sharded over a 4-chip "
                         "data mesh, against the single-device program")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.chips == 1:
        os.environ["DATAX_FUSION_MESH"] = "0"   # exactly one device in use

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    log("device", platform=dev.platform, device_kind=dev.device_kind,
        count=len(devices), chips_used=args.chips, seed=args.seed,
        jax=jax.__version__, compile_cache=cache)
    if args.chips == 1:
        serve_phase(args.seed)
        gc.collect()
        fused_phase(args.seed, 1)
        kernel_phase(args.seed)
    else:
        fused_phase(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
