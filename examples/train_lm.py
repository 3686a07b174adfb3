"""End-to-end training driver: train an LM through the DataX pipeline.

The data pipeline is a **v2 fluent-DSL app** (the last spec-style holdout
migrated): corpus sensor -> packer AU -> batcher AU, wired with decorators
and ``.via`` combinators; the Trainer attaches to the resulting ``batches``
stream as just another subscriber (§3 stream reuse) and drives the pjit
train-step device AU -> {async checkpoints, metrics}.  Fault tolerance is
live: Ctrl-C (or --preempt-at) triggers the preemption path (blocking
checkpoint, clean exit); re-running the same command resumes.

CPU-sized default (a few M params).  On a real slice, pass --preset 100m
and scale steps/batch.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 100
"""
import argparse
import dataclasses

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig, RunConfig
from repro.core import App, Operator
from repro.data import corpus as corpus_mod
from repro.data import pipeline as pipe
from repro.train.trainer import Trainer, TrainerConfig


def preset_config(name: str) -> ModelConfig:
    if name == "tiny":          # ~4M params: runs on this CPU container
        return dataclasses.replace(
            get_smoke_config("qwen3-14b"), n_layers=4, d_model=128,
            n_heads=4, n_kv_heads=2, d_ff=512, vocab=4096, head_dim=32)
    if name == "100m":          # ~100M params: for real hardware
        return dataclasses.replace(
            get_smoke_config("qwen3-14b"), n_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, d_ff=2048, vocab=32768, head_dim=64)
    raise SystemExit(f"unknown preset {name}")


def pipeline_app(cfg: ModelConfig, tcfg: TrainerConfig) -> App:
    """corpus -> packer -> batcher, declared fluently.

    The business logic is the shared library AUs (repro.data) — the app
    only *wires* them, which is the v1-vs-v2 productivity delta."""
    app = App("train-pipeline")
    app.driver(corpus_mod.corpus_driver, name="corpus",
               emits=corpus_mod.CORPUS_SCHEMA, config=corpus_mod.CORPUS_CONFIG)
    app.analytics_unit(pipe.packer_au, name="packer",
                       emits=pipe.PACKED_SCHEMA, config=pipe.PACKER_CONFIG,
                       max_instances=4)
    app.analytics_unit(pipe.batcher_au, name="batcher",
                       emits=pipe.BATCH_SCHEMA, config=pipe.BATCHER_CONFIG,
                       max_instances=1)
    docs = app.sense("docs", "corpus", vocab=cfg.vocab, seed=tcfg.seed)
    sequences = docs.via("packer", name="sequences", seq_len=tcfg.seq_len)
    # the batcher accumulates across messages -> single instance; .tap()
    # promises `batches` to its external subscriber (the Trainer)
    sequences.via("batcher", name="batches", batch=tcfg.global_batch,
                  fixed_instances=1).tap()
    return app


def build_app() -> App:
    """CPU-sized pipeline app with default knobs — the entry point
    ``datax check`` discovers (main() parameterizes via pipeline_app)."""
    return pipeline_app(preset_config("tiny"), TrainerConfig())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="/tmp/repro-train-example")
    ap.add_argument("--preempt-at", type=int, default=0,
                    help="simulate preemption after N steps")
    args = ap.parse_args()

    cfg = preset_config(args.preset)
    run = RunConfig(attention_impl="chunked", attention_chunk=128,
                    remat="none", learning_rate=3e-3, warmup_steps=20)
    tcfg = TrainerConfig(global_batch=args.batch, seq_len=args.seq,
                         ckpt_every=25, total_steps=args.steps,
                         workdir=args.workdir)

    op = Operator(reconcile_interval_s=0.2)
    pipeline_app(cfg, tcfg).deploy(op, start_sensors=False)
    op.start()
    tr = Trainer(cfg, run, tcfg, operator=op, deploy_pipeline=False)
    tr.init_or_restore()
    op.start_pending_sensors()   # no data flows before the trainer subscribed
    if tr.step:
        print(f"resumed from checkpoint at step {tr.step}")
    print(f"training {cfg.param_count()/1e6:.1f}M params "
          f"({args.preset}); target {args.steps} steps")
    try:
        while tr.step < args.steps:
            if args.preempt_at and tr.step >= args.preempt_at:
                print("simulating preemption notice...")
                tr.preemption.preempt()
            got = tr.run_steps(min(10, args.steps - tr.step))
            if not got:
                break
            m = got[-1]
            print(f"step {m['step']:4d}  loss {m['loss']:.4f}  "
                  f"lr {m['lr']:.2e}  {m['step_time_s']*1e3:.0f} ms/step"
                  + ("  [straggler]" if m["straggler"] else ""))
    except KeyboardInterrupt:
        print("interrupted: writing preemption checkpoint")
        tr.preemption.preempt()
        tr.run_steps(1)
    finally:
        tr.close()
        op.shutdown()
    print(f"done at step {tr.step}; checkpoints in {args.workdir}/ckpt")


if __name__ == "__main__":
    main()
