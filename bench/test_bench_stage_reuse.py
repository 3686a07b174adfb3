"""CPU tests of the reader of ``stage_reuse.replay``: the share of the
window's batched bursts that the fused unit stacked wholly into host
buffers it kept from earlier bursts, on hand-built runs, and None on a
program that keeps no such counter (one older than the kept buffers)."""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = "stage_reuse.replay"


def _run(c0: dict, c1: dict) -> harness.Run:
    return harness.Run(window=(0.0, 51.0), setup_s=1.0, records={}, info={},
                       counters=(c0, c1), steps=[], trace=None, peaks={},
                       conf={}, chips=1)


def _read(c0: dict, c1: dict):
    return harness.load_module(BENCH / "metrics" / f"{NAME}.py").read(
        _run(c0, c1))


@pytest.mark.parametrize("reuses, want", [(405, 100.0), (205, 50.0)])
def test_stage_reuse_reads_the_share_of_bursts_staged_in_kept_buffers(
        reuses, want):
    c0 = {"batched_bursts": 10, "stage_reuses": 5}
    c1 = {"batched_bursts": 410, "stage_reuses": reuses}
    assert _read(c0, c1) == pytest.approx(want)


def test_stage_reuse_is_none_when_no_burst_ran_in_the_window():
    c0 = {"batched_bursts": 10, "stage_reuses": 5}
    assert _read(c0, dict(c0)) is None


def test_stage_reuse_is_none_on_a_program_without_the_counters():
    older = {"batched_bursts": 10, "resident_links": 0}
    assert _read(older, {**older, "batched_bursts": 410}) is None


def test_stage_reuse_is_in_the_benchmark():
    m = {m["name"]: m for m in harness.load_benchmark()["per_layer"]}[NAME]
    assert (m["unit"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("%", "program_counter",
                                "executor pump and fusion", "frames_per_s",
                                ["camera-vga.replay"])
