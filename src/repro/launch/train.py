"""Training launcher: ``python -m repro.launch.train --arch qwen3-32b ...``

On this CPU container it builds a (1,1) host mesh and a REDUCED config by
default (--full uses the assigned dims — only sensible on a real slice).
On hardware, the same entry point runs under the multi-host runtime
(jax.distributed.initialize is called when JAX_COORDINATOR is set) with the
production mesh from repro.launch.mesh.
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="use the assigned full config (real hardware)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="/tmp/repro-launch-train")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="mesh data-axis size (0 = all devices)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("JAX_COORDINATOR"):  # multi-host entry
        import jax
        jax.distributed.initialize()

    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    n_dev = len(jax.devices())
    data = args.data_axis or max(1, n_dev // args.model_axis)
    mesh = make_host_mesh(data, args.model_axis)
    run = RunConfig(attention_impl="chunked", attention_chunk=256,
                    remat="full" if args.full else "none",
                    microbatches=args.microbatches,
                    grad_compression=args.grad_compression,
                    zero3=args.full)
    tcfg = TrainerConfig(global_batch=args.batch, seq_len=args.seq,
                         ckpt_every=25, total_steps=args.steps,
                         workdir=args.workdir)
    tr = Trainer(cfg, run, tcfg, mesh=mesh)
    tr.init_or_restore()
    print(f"arch={args.arch} params={cfg.param_count()/1e6:.1f}M "
          f"mesh=({data},{args.model_axis}) resume_step={tr.step}")
    while tr.step < args.steps:
        got = tr.run_steps(min(10, args.steps - tr.step))
        if not got:
            break
        m = got[-1]
        print(f"step {m['step']:5d} loss {m['loss']:.4f} "
              f"{m['step_time_s']*1e3:.0f}ms")
    tr.close()


if __name__ == "__main__":
    main()
