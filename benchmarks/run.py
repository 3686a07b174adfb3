"""Benchmark harness — one entry per paper claim/figure (DESIGN.md §9).

Prints ``name,us_per_call,derived`` CSV.  Benchmarks whose ``run()`` returns a
dict also get a machine-readable artifact ``BENCH_<name>.json`` (variant ->
metric) for CI trending and gating.  Run:

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--gate] [--out-dir D]

``--gate`` turns known regression checks into hard failures — today: the
fused device chain must beat per-hop bus execution (BENCH_fusion.json
``speedup`` > 1); batched fused execution must beat per-message jitted
dispatch on the jax leg (``batched_msgs_per_s`` >= ``fused_jit_msgs_per_s``);
4 queue-grouped workers must beat 1 by >= 2x on the
scaling pipeline (BENCH_scaling.json ``speedup``); 4 keyed *stateful*
workers must beat 1 by >= 2x with zero per-key ordering violations and zero
lost state across a forced mid-run scale-down (BENCH_keyed.json); coalesced
wire frames must be >= 2x per-message framing with exactly-once accounting
across a mid-run kill and a correctly negotiated codec on BOTH legs — zstd
with a compression win where zstandard is installed, a clean negotiate-down
to zlib where it is not (BENCH_wire.json); work stealing must recover
>= 1.5x over a pinned straggler pool with zero keyed ordering violations
(BENCH_scaling.json ``steal_*``); and
publishing on a durable subject must cost <= 2x fire-and-forget, with a
late joiner replaying the full retained history (BENCH_durable.json).  Modules
are imported lazily so a minimal-deps environment (no jax) can still run the
core benchmarks — the scaling and keyed gates are pure platform code and run
on both CI legs.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import traceback

ALL = {
    "bus": "bench_bus",
    "pipeline": "bench_pipeline",
    "autoscale": "bench_autoscale",
    "scaling": "bench_scaling",
    "keyed": "bench_keyed",
    "durable": "bench_durable",
    "transport": "bench_transport",
    "wire": "bench_wire",
    "loc": "bench_loc",
    "reuse": "bench_reuse",
    "fusion": "bench_fusion",
    "mesh": "bench_mesh",
    "kernels": "bench_kernels",
    "compression": "bench_compression",
    "serve": "bench_serve",
    "train": "bench_train",
}


def _gate(results: dict[str, dict]) -> list[str]:
    """Regression checks over the collected metric dicts."""
    failures = []
    fusion = results.get("fusion")
    if fusion is not None and fusion.get("speedup", 0.0) <= 1.0:
        failures.append(
            f"fusion: fused chain not faster than per-hop bus "
            f"(fused={fusion.get('fused_msgs_per_s')} msgs/s, "
            f"bus={fusion.get('bus_msgs_per_s')} msgs/s)")
    if fusion is not None and "batched_msgs_per_s" in fusion \
            and fusion["batched_msgs_per_s"] < fusion.get(
                "fused_jit_msgs_per_s", 0.0):
        failures.append(
            f"fusion: batched fused execution slower than per-message "
            f"jitted dispatch "
            f"(batched={fusion.get('batched_msgs_per_s')} msgs/s, "
            f"per-message={fusion.get('fused_jit_msgs_per_s')} msgs/s, "
            f"max_batch={fusion.get('max_batch')})")
    mesh = results.get("mesh")
    if mesh is not None and "skipped" not in mesh:
        if mesh.get("bit_identical") is not True:
            failures.append(
                "mesh: sharded outputs must be bit-identical to the "
                "single-device batched program and the host-composed chain")
        if mesh.get("sharded_bursts", 0) <= 0:
            failures.append(
                "mesh: the sharded path never executed (silent fallback to "
                "the single-device batched program)")
        if mesh.get("speedup", 0.0) < 1.0:
            failures.append(
                f"mesh: sharded fused bursts must not be slower than "
                f"single-device batched under "
                f"{mesh.get('devices')} devices (got "
                f"{mesh.get('speedup')}x; "
                f"sharded={mesh.get('sharded_msgs_per_s')} msgs/s, "
                f"batched={mesh.get('batched_msgs_per_s')} msgs/s)")
    scaling = results.get("scaling")
    if scaling is not None and scaling.get("speedup", 0.0) < 2.0:
        workers = scaling.get("workers", 4)
        failures.append(
            f"scaling: {workers} grouped workers must be >=2x over 1 "
            f"(got {scaling.get('speedup')}x; "
            f"pooled={scaling.get(f'grouped_{workers}_msgs_per_s')} msgs/s, "
            f"single={scaling.get('grouped_1_msgs_per_s')} msgs/s)")
    if scaling is not None and scaling.get("dropped", 0) > 0:
        failures.append(
            f"scaling: benchmark pipeline dropped "
            f"{scaling.get('dropped')} messages (should be lossless)")
    keyed = results.get("keyed")
    if keyed is not None:
        if keyed.get("speedup", 0.0) < 2.0:
            workers = keyed.get("workers", 4)
            failures.append(
                f"keyed: {workers} keyed stateful workers must be >=2x over "
                f"1 (got {keyed.get('speedup')}x; "
                f"pooled={keyed.get(f'keyed_{workers}_msgs_per_s')} msgs/s, "
                f"single={keyed.get('keyed_1_msgs_per_s')} msgs/s)")
        if keyed.get("ordering_violations", 1) != 0:
            failures.append(
                f"keyed: {keyed.get('ordering_violations')} per-key ordering "
                f"violations under scale-down churn (must be 0)")
        if keyed.get("lost_state", 1) != 0:
            failures.append(
                f"keyed: {keyed.get('lost_state')} per-key state "
                f"resets/forks across rebalance (must be 0)")
        if keyed.get("dropped", 0) > 0:
            failures.append(
                f"keyed: benchmark pipeline dropped "
                f"{keyed.get('dropped')} messages (should be lossless)")
    transport = results.get("transport")
    if transport is not None:
        if transport.get("lost", 1) != 0:
            failures.append(
                f"transport: {transport.get('lost')} messages lost across "
                f"the worker-process kill (must be 0)")
        if transport.get("duplicates", 1) != 0:
            failures.append(
                f"transport: {transport.get('duplicates')} double-deliveries "
                f"across the worker-process kill (must be 0)")
        if transport.get("ordering_violations", 1) != 0:
            failures.append(
                f"transport: {transport.get('ordering_violations')} per-key "
                f"ordering violations across the cross-process re-home "
                f"(must be 0)")
        if transport.get("delivered", -1) != transport.get("published", 0):
            failures.append(
                f"transport: delivered {transport.get('delivered')} of "
                f"{transport.get('published')} published messages")
    wire = results.get("wire")
    if wire is not None:
        if wire.get("coalesced_x", 0.0) < 2.0:
            failures.append(
                f"wire: coalesced frames must be >=2x per-message framing "
                f"(got {wire.get('coalesced_x')}x; "
                f"coalesced={wire.get('coalesced_msgs_per_s')} msgs/s, "
                f"per-message={wire.get('per_message_msgs_per_s')} msgs/s)")
        if wire.get("frames_coalesced", 0) <= 0:
            failures.append(
                "wire: the coalesced path never shipped a multi-message "
                "frame (silent fallback to per-message framing)")
        if wire.get("zstd_host"):
            # full-deps leg: the negotiated codec must be zstd and the wire
            # must actually be smaller than the raw payloads
            if wire.get("codec") != "zstd":
                failures.append(
                    f"wire: zstd available but negotiated codec is "
                    f"{wire.get('codec')!r} (must be 'zstd')")
            if not wire.get("wire_ratio") or wire["wire_ratio"] <= 1.0:
                failures.append(
                    f"wire: raw/compressed ratio must be > 1 on the zstd "
                    f"leg (got {wire.get('wire_ratio')})")
        elif not wire.get("negotiated_down"):
            # minimal-deps leg: a zlib-only host must negotiate DOWN to
            # zlib cleanly, not fail or stay un-negotiated
            failures.append(
                f"wire: zstd-less host must negotiate down to zlib "
                f"(codec={wire.get('codec')!r}, "
                f"proto={wire.get('proto')})")
        for k in ("lost", "duplicates", "ordering_violations"):
            if wire.get(k, 1) != 0:
                failures.append(
                    f"wire: {wire.get(k)} {k} across the coalesced-frame "
                    f"kill run (must be 0)")
    if scaling is not None and "steal_speedup" in scaling:
        if scaling.get("steal_speedup", 0.0) < 1.5:
            failures.append(
                f"scaling: work stealing must recover >=1.5x over the "
                f"pinned straggler pool (got {scaling.get('steal_speedup')}x; "
                f"stealing={scaling.get('steal_stealing_msgs_per_s')} msgs/s, "
                f"pinned={scaling.get('steal_pinned_msgs_per_s')} msgs/s)")
        if scaling.get("stolen", 0) <= 0:
            failures.append(
                "scaling: the steal path never moved a partition "
                "(stolen == 0 with stealing enabled)")
        if scaling.get("steal_ordering_violations", 1) != 0:
            failures.append(
                f"scaling: {scaling.get('steal_ordering_violations')} "
                f"per-key ordering violations under work stealing "
                f"(must be 0)")
        if scaling.get("steal_lost_state", 1) != 0:
            failures.append(
                f"scaling: {scaling.get('steal_lost_state')} per-key state "
                f"resets/forks under work stealing (must be 0)")
    durable = results.get("durable")
    if durable is not None:
        if durable.get("publish_overhead_x", 99.0) > 2.0:
            failures.append(
                f"durable: publishing on a durable subject must cost <= 2x "
                f"fire-and-forget (got {durable.get('publish_overhead_x')}x; "
                f"plain={durable.get('plain_msgs_per_s')} msgs/s, "
                f"durable={durable.get('durable_msgs_per_s')} msgs/s)")
        if durable.get("replayed_records", -1) != durable.get("log_depth", 0):
            failures.append(
                f"durable: late-joiner replay must drain the full retained "
                f"history (replayed {durable.get('replayed_records')} of "
                f"{durable.get('log_depth')} records)")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(ALL), default=None)
    ap.add_argument("--gate", action="store_true",
                    help="fail on known benchmark regressions (CI)")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<name>.json artifacts are written")
    args = ap.parse_args()
    try:
        import jax  # noqa: F401
    except Exception:
        pass  # minimal-deps leg: nothing is compiled
    else:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    failed = 0
    results: dict[str, dict] = {}
    for name, modname in ALL.items():
        if args.only and name != args.only:
            continue
        try:
            mod = importlib.import_module(f".{modname}", package=__package__)
            data = mod.run()
            if isinstance(data, dict):
                results[name] = data
                path = out_dir / f"BENCH_{name}.json"
                path.write_text(json.dumps(data, indent=2, sort_keys=True)
                                + "\n")
                print(f"{name},0.0,artifact={path}")
        except Exception:
            failed += 1
            print(f"{name},-1,FAILED")
            traceback.print_exc()
    if args.gate:
        for failure in _gate(results):
            failed += 1
            print(f"GATE FAILED: {failure}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
