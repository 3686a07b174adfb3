"""CPU tests of the readers of the program's span counters: each on a
hand-built run whose counters give a value worked out by hand, None when
its divisor did not move, and None on a program that keeps no such
counter (one older than the spans)."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

# metric -> (counters at the window's start, at its end, value by hand)
CASES = {
    "mailbox_wait_ms.replay": (
        {"mailbox_wait_s": 1.0, "mailbox_msgs": 100},
        {"mailbox_wait_s": 24.0, "mailbox_msgs": 200},
        230.0),                             # 23 s over 100 frames
    "to_device_ms.replay": (
        {"stack_s": 1.0, "upload_s": 2.0, "batched_bursts": 10},
        {"stack_s": 9.0, "upload_s": 6.5, "batched_bursts": 260},
        50.0),                              # (8 + 4.5) s over 250 bursts
    "from_device_ms.replay": (
        {"download_s": 0.0, "unstack_s": 0.0, "batched_bursts": 0},
        {"download_s": 3.0, "unstack_s": 2.0, "batched_bursts": 250},
        20.0),                              # 5 s over 250 bursts
    "emit_ms.replay": (
        {"emit_s": 0.5, "batches": 10},
        {"emit_s": 0.75, "batches": 510},
        0.5),                               # 0.25 s over 500 pulls
    "slot_wait_ms": (
        {"slot_wait_s": 0, "prefills": 0},
        {"slot_wait_s": 700.0, "prefills": 200},
        3500.0),                            # 700 s over 200 requests
    "engine_host_ms": (
        {"tick_s": 0, "device_wait_s": 0, "ticks": 0},
        {"tick_s": 51.0, "device_wait_s": 47.0, "ticks": 1000},
        4.0),                               # (51 - 47) s over 1,000 ticks
}
DIVISOR = {"mailbox_wait_ms.replay": "mailbox_msgs",
           "to_device_ms.replay": "batched_bursts",
           "from_device_ms.replay": "batched_bursts",
           "emit_ms.replay": "batches", "slot_wait_ms": "prefills",
           "engine_host_ms": "ticks"}


def _run(c0: dict, c1: dict) -> harness.Run:
    return harness.Run(window=(0.0, 51.0), setup_s=1.0, records={}, info={},
                       counters=(c0, c1), steps=[], trace=None, peaks={},
                       conf={}, chips=1)


def _reader(name: str):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_reads_the_hand_computed_value(name):
    c0, c1, want = CASES[name]
    assert _reader(name).read(_run(c0, c1)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_none_when_its_divisor_did_not_move(name):
    c0, c1, _ = CASES[name]
    key = DIVISOR[name]
    assert _reader(name).read(_run(c0, {**c1, key: c0[key]})) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_none_on_a_program_without_the_counters(name):
    c0, c1, _ = CASES[name]
    older = {DIVISOR[name]: 0, "batches": 0}     # counters it did keep
    assert _reader(name).read(_run(older, {**older, DIVISOR[name]: 9})) \
        is None


def test_every_span_metric_is_in_the_benchmark():
    spec = harness.load_benchmark()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in CASES:
        m = entries[name]
        assert m["source"] == "program_counter" and m["unit"] == "ms"
        assert m["workloads"]
