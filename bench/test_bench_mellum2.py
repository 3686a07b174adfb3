"""The mellum2-12b-a2.5b cell driven end to end on the CPU at a size a test
run can hold, through the harness's own ``run`` (everything but the look
for a chip): a sound run comes out correct over requests that outgrew the
window's ring, and the control and every fault planted in the timed path
underneath come out not correct.  Its yardstick and readers are checked
by hand."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import faults  # noqa: E402
import faults_swa_moe  # noqa: E402
import harness  # noqa: E402
from flops_swa_moe import SwaMoeDims  # noqa: E402
from peaks import peaks_for  # noqa: E402

WORKLOAD = "mellum2-12b-a2.5b.code64"
SEED = 2 ** 32 + 54321          # wider than 32 bits, as run seeds may be
# every width cut, the pattern (3 sliding : 1 full, two periods), YaRN and
# top-k routing kept; a window of 8 that most requests outgrow, and YaRN's
# original context cut with it, so the full layers' frequencies move at
# these lengths.  Activations in float32: with 2 of 8 experts a token at
# this width takes the other expert of a bfloat16 near-tie often enough to
# set the compared number, so the faults are judged against an exact
# program here, and bfloat16's own reading is taken at full size.
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
MELLUM = {"hidden_size": 64, "moe_intermediate_size": 32,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
          "sliding_window": 8, "rope_parameters": ROPE,
          "torch_dtype": "float32",
          "serving": {"kv_slots": 4, "max_seq": 128, "attention_impl":
                      "chunked", "attention_chunk": 32, "eos": None},
          # a few hundred served tokens compared, as at full size
          "reference": {"sample_requests": 16}}
CODE = {"clients": 6, "warm_seconds": 0.5,
        "sizes": {"prompt_len": {"lognormal": {"median": 24, "sigma": 0.8},
                                 "min": 4, "max": 72},
                  "answer_len": {"lognormal": {"median": 12, "sigma": 0.7},
                                 "min": 2, "max": 48}}}


@pytest.fixture(autouse=True)
def short_settle(monkeypatch):
    # a fault that loses answers would otherwise wait a minute for them
    monkeypatch.setattr(harness, "SETTLE_S", 5.0)


def run(hook=None):
    return harness.run(WORKLOAD, SEED, 1.0, False, started=time.monotonic(),
                       overrides=MELLUM, mix_overrides=CODE,
                       peaks=peaks_for("TPU v5 lite"), hook=hook)


def test_sound_run_is_correct_over_wrapped_rings():
    def seen(cell, load, window):
        return {"compared": cell.compared, "ring": cell.ring(),
                "steps": len(cell.steps)}
    r = run(hook=seen)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"tokens_per_s", "setup_s"} <= set(r["metrics"])
    assert r["compiles_in_window"] == 0
    assert r["counters"]["moe_dropped"] == 0
    assert r["counters"]["moe_routed_pairs"] > 0
    compared = r["readings"]["compared"]
    assert compared["requests"] > 1
    # every compared request outgrew the ring: its decode wrapped it
    assert compared["positions"] > compared["requests"] * r["readings"]["ring"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", ["serve_control", "window_short",
                                  "ring_at_pos", "capacity_drops",
                                  "yarn_off"])
def test_control_and_faults_are_not_correct(name):
    planted = {**faults.FAULTS, **faults_swa_moe.FAULTS}[name]
    with planted():
        r = run()
    assert not r["correct"], (name, r["checks"])


def _conf():
    import json
    return json.loads((BENCH / "configs" / "mellum2-12b-a2.5b.json")
                      .read_text())


def test_yardstick_at_published_widths():
    dm = SwaMoeDims.from_config(_conf())
    assert (dm.n_full, dm.n_sliding, dm.window) == (2, 6, 1024)
    # attention 21.2 M, one expert 6.19 M, the router 0.15 M per layer
    assert dm.attn_params == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert dm.expert_params == 3 * 2304 * 896
    layer = dm.attn_params + 64 * dm.expert_params + 2304 * 64
    assert abs(layer - 417.7e6) < 0.1e6
    # window pairs: all causal pairs up to the window, then W per query
    assert dm.window_pairs(1024) == 1024 * 1025 // 2
    assert dm.window_pairs(3000) == 1024 * 1025 // 2 + 1976 * 1024
    # a decode step at 64 slots hitting every expert of every layer reads
    # the eight layers' weights, the head, and the KV it attends to
    step = dm.decode_bytes(64, 64 * 2048, 64 * 1024, 8 * 64)
    weights = (8 * layer + 98304 * 2304) * 2
    assert weights < step < weights + 2.0e9
    # active experts only: top-8 of 64
    per_token = dm.token_flops()
    assert per_token == 2.0 * 8 * (dm.attn_params + 2304 * 64
                                   + 8 * dm.expert_params)


def test_readers_read_none_without_the_new_records():
    import readers_swa_moe as rs

    class Run:
        conf = _conf()
        trace = None

        def steps_in_window(self):
            return [{"t": 0.0, "prefill": [100], "active": 4,
                     "attended": 400}]
    assert rs.work(Run()) is None
    assert rs.op_device_s(Run(), rs.EXPERT_KERNEL) is None


def test_expert_kernel_found_by_its_instruction_name():
    """The device trace names an operation by its whole instruction; the
    reader matches the instruction's name, with or without a suffix."""
    import readers_swa_moe as rs
    gmm = ("%expert_gmm.3 = f32[512,896]{1,0:T(8,128)S(1)} custom-call("
           "%get-tuple-element.93, %pad_add_fusion.1), custom_call_target="
           '"tpu_custom_call"')
    assert rs.is_op(gmm, rs.EXPERT_KERNEL)
    assert rs.is_op("%expert_gmm = f32[8,8]{1,0} custom-call()",
                    rs.EXPERT_KERNEL)
    assert not rs.is_op("%while.107 = (s32[], f32[8]) while(%t), "
                        "body=%expert_gmm_body", rs.EXPERT_KERNEL)
    assert not rs.is_op("%expert_gmm_2.1 = f32[8]{0} fusion()",
                        rs.EXPERT_KERNEL)
