"""Serving launcher: ``python -m repro.launch.serve --arch qwen3-32b ...``

Reduced config on CPU (--full for real slices).  Drives the continuous-
batching engine with a synthetic request stream and prints latency stats.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np

    from repro import models
    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import RunConfig
    from repro.serve import ServeEngine

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    run = RunConfig(attention_impl="chunked", attention_chunk=256,
                    remat="none")
    params = models.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, run, params, n_slots=args.slots,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(4, min(64, args.max_seq // 2)))
        eng.submit(f"req-{i:04d}", list(rng.integers(1, cfg.vocab, plen)),
                   max_new_tokens=args.max_new)
    done = eng.run_until_idle()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    ttfts = sorted((r.first_token_at - r.arrived) * 1e3 for r in done)
    print(f"arch={args.arch} served={len(done)} tokens={toks} "
          f"tok/s={toks/dt:.0f} ttft_p50={ttfts[len(ttfts)//2]:.0f}ms "
          f"ttft_p99={ttfts[int(len(ttfts)*0.99)]:.0f}ms")
    print("engine:", eng.metrics)


if __name__ == "__main__":
    main()
