import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ must precede all other imports (see dryrun.py)

"""Perf hillclimb driver — hypothesis -> change -> re-lower -> re-analyse.

Three cells (chosen per the assignment from the baseline roofline table):

  A. granite-moe-3b-a800m × train_4k — WORST roofline fraction (0.1%,
     useful-FLOPs ratio 0.02: the dense [G,S,E,C] dispatch dominates tiny
     experts).  Iterations target the dominant memory/compute waste.
  B. grok-1-314b × train_4k — MOST COLLECTIVE-BOUND (75 s collective vs
     26 s compute at baseline).  Iterations target wire bytes.
  C. qwen3-32b × decode_32k — most representative of the paper's technique
     (the serving/stream-exchange path).  Iterations target HBM traffic.

Each variant compiles the cell with RunConfig overrides and records the
three roofline terms to experiments/perf/<cell>__<tag>.json.  The narrative
log (hypothesis / before / after / verdict) lives in EXPERIMENTS.md §Perf.

Usage:
  python -m repro.launch.hillclimb --cell A [--variant name | --all]
"""
import argparse
import dataclasses
import sys
import traceback

from repro.configs import SHAPES_BY_NAME, get_config
from repro.launch import presets
from repro.launch.dryrun import run_cell

CELLS = {
    "A": ("granite-moe-3b-a800m", "train_4k"),
    "B": ("grok-1-314b", "train_4k"),
    "C": ("qwen3-32b", "decode_32k"),
}

# variant name -> RunConfig overrides
VARIANTS = {
    "A": {
        "baseline": {},
        "group512": {"moe_group_size": 512},
        "group256": {"moe_group_size": 256},
        "group128": {"moe_group_size": 128},
        "group512_mb8": {"moe_group_size": 512, "microbatches": 8},
        "group512_dots": {"moe_group_size": 512, "remat": "dots"},
        # lean_* run AFTER the moe.py lean-routing rewrite (bool/i32
        # intermediates instead of f32 one-hots); same RunConfig as their
        # pre-rewrite counterparts -> isolates the code change
        "lean2048": {},
        "lean512": {"moe_group_size": 512},
        "lean512_mb8": {"moe_group_size": 512, "microbatches": 8},
    },
    "B": {
        "baseline": {},
        "dots": {"remat": "dots"},
        "seqpar": {"seq_parallel": True},
        "dots_seqpar": {"remat": "dots", "seq_parallel": True},
        "expert_data": {"expert_axis": "data"},
        "mb8": {"microbatches": 8},
        "mb8_dots": {"microbatches": 8, "remat": "dots"},
        "gacc_bf16": {"grad_accum_dtype": "bfloat16"},
        "mb8_noremat": {"microbatches": 8, "remat": "none"},
        "mb8_gacc_bf16": {"microbatches": 8,
                          "grad_accum_dtype": "bfloat16"},
    },
    "C": {
        "baseline": {},
        "chunked_attn": {"decode_attn_impl": "chunked",
                         "attention_chunk": 2048},
        "chunked_attn_512": {"decode_attn_impl": "chunked",
                             "attention_chunk": 512},
    },
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS), required=True)
    ap.add_argument("--variant", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/perf")
    args = ap.parse_args()

    arch, shape_name = CELLS[args.cell]
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    base_run = presets.run_preset(cfg, shape)
    names = list(VARIANTS[args.cell]) if args.all else [args.variant]
    for name in names:
        overrides = VARIANTS[args.cell][name]
        run = dataclasses.replace(base_run, **overrides)
        try:
            r = run_cell(arch, shape_name, multi_pod=False,
                         out_dir=args.out_dir, run=run,
                         tag=f"{args.cell}-{name}")
            print(f"{args.cell}/{name}: compute={r['compute_s']:.3f}s "
                  f"memory={r['memory_s']:.3f}s "
                  f"collective={r['collective_s']:.3f}s "
                  f"bottleneck={r['bottleneck']} "
                  f"useful={r['useful_flops_ratio']:.3f} "
                  f"frac={r['roofline_fraction']*100:.2f}% "
                  f"peak={r['peak_memory_bytes']/2**30:.1f}GiB")
            sys.stdout.flush()
        except Exception:
            print(f"{args.cell}/{name}: FAILED\n{traceback.format_exc()}")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
