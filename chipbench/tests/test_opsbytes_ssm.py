"""``opsbytes_ssm`` against the arithmetic of the issue that added
``granite-4.0-h-small`` (PR 42), and the two readers built on it on a
hand-made ``obs``."""

import json
import os

import pytest

from chipbench import opsbytes_ssm
from chipbench.layer_metrics import (
    ssm_chunk_roofline_pct,
    ssm_decode_roofline_pct,
)

HERE = os.path.dirname(os.path.abspath(__file__))
EXPERT, TOTAL, EMBED = 9_437_184, 4_757_211_776, 205_520_896
A_STATE = 4 * 128 * 64 * 128 + 2 * 3 * 8448     # a slot-layer, one way
POSITION = 2 * 8 * 128 * 2                      # ONE softmax layer: 4 KiB


def spec():
    with open(os.path.join(
            HERE, "..", "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


def test_counts_are_the_issues_arithmetic():
    c = opsbytes_ssm.counts(spec())
    assert c["ssm_matmul"] == 4096 * 16_768 + 33_554_432
    assert c["softmax_matmul"] == 41_943_040
    assert c["expert"] == EXPERT and c["shared"] == 2 * EXPERT
    assert c["router"] == 294_912 and c["held"] == 36
    assert c["total"] == TOTAL                   # 8.86 GiB of bfloat16
    assert opsbytes_ssm.layer_kinds(spec()) == (9, 1)
    assert opsbytes_ssm.state_values(spec()) == 128 * 64 * 128   # 4 MiB
    assert opsbytes_ssm.conv_tail_values(spec()) == 3 * 8448
    assert opsbytes_ssm.head_dim(spec()) == 128


def test_a_decode_step_counts_the_rows_decoded_and_what_is_read():
    s = spec()
    assert opsbytes_ssm.state_bytes(s, 1) == 9 * 2 * A_STATE
    assert opsbytes_ssm.cache_bytes(s, [999, 2499]) == 3500 * POSITION
    every = opsbytes_ssm.decode_step(s, [2499, 999], 1.0, 5.0)
    none = opsbytes_ssm.decode_step(s, [2499, 999], 0.0, 5.0)
    assert every["expert_bytes"] == 2 * 10 * 36 * EXPERT
    assert every["bytes"] - none["bytes"] == every["expert_bytes"]
    # held weights outside the experts once — the tied embedding as the
    # head once, of it as the embedding two rows —, the state of the two
    # rows decoded, the softmax layer's live positions
    assert none["bytes"] == 2 * (TOTAL - 10 * 36 * EXPERT) + 2 * 9 * 2 \
        * A_STATE + 3500 * POSITION + 2 * 4096 * 2
    # forty-eight slots' states are not what two rows need
    assert every["state_bytes"] == 2 * 9 * 2 * A_STATE
    # the cell's step: 48 rows at ~2.5k positions; the state is over a
    # quarter of what must move, and bytes bound it, not operations
    full = opsbytes_ssm.decode_step(s, [2500] * 48, 0.999, 5.0)
    assert 0.25 < full["state_bytes"] / full["bytes"] < 0.3
    assert 7 < full["state_bytes"] / full["cache_bytes"] < 8
    assert full["bytes"] / 819e9 > 5 * full["flops"] / 197e12
    assert 0.015 < full["bytes"] / 819e9 < 0.017


def test_a_chunk_counts_one_slots_state_and_the_weights_once():
    s = spec()
    whole = opsbytes_ssm.prefill_chunk(s, 1024, 512, 1.0, 5.0)
    assert whole["bytes"] == 2 * TOTAL + 9 * 2 * A_STATE + (
        1024 + 2 * 512) * POSITION + 2 * 4096 * 512
    assert whole["state_bytes"] == 9 * 2 * A_STATE
    fewer = opsbytes_ssm.prefill_chunk(s, 1024, 512, 0.5, 5.0)
    assert whole["bytes"] - fewer["bytes"] == 10 * 18 * EXPERT * 2
    # a token: the weights' products, the write and the read with the
    # state in each state-space layer, its pair in the softmax layer;
    # the head once
    one = opsbytes_ssm.prefill_chunk(s, 0, 1, 1.0, 5.0)
    c = opsbytes_ssm.counts(s)
    assert one["flops"] == 2.0 * (
        9 * c["ssm_matmul"] + c["softmax_matmul"]
        + 10 * (c["shared"] + c["router"] + 5.0 * c["expert"])
        + c["head"]) + 9 * 2 * 2 * 128 * 64 * 128 + 2 * 2 * 32 * 128
    # bound by the weights' bytes at the chunk the cell uses
    assert whole["bytes"] / 819e9 > whole["flops"] / 197e12


def _obs(programs=None, config=None, **engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 512},
            "window_wall": 1000.0, "config": config or spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(2048, [10.0 + 0.03 * i
                                            for i in range(400)])] * 40},
            "trace": programs and {"devices": [{"programs": programs}]}}


def test_ssm_decode_roofline_is_least_time_over_step_time():
    counters = dict(moe_decode_experts_hit=355, moe_decode_expert_slots=360,
                    moe_decode_assignments=200, moe_decode_rows_routed=400,
                    recurrent_decode_rows=360)
    programs = {"jit__decode": {"count": 100, "total_s": 3.0},
                "jit__sample_batch": {"count": 100, "total_s": 0.05}}
    got = ssm_decode_roofline_pct.read(_obs(programs, **counters))
    # forty rows at contexts of about 2,250: what must be moved at
    # 819 GB/s over the 30 ms a step took
    need = opsbytes_ssm.decode_step(spec(), [2250] * 40, 355 / 360, 5.0)
    assert got == pytest.approx(
        100 * need["bytes"] / 819e9 / 30e-3, rel=0.02)
    assert 0 < got < 100
    # no device trace -> no step time -> nothing; a program without the
    # recurrent counters, or another family's configuration: nothing
    assert ssm_decode_roofline_pct.read(_obs(None, **counters)) is None
    solar = json.load(open(os.path.join(HERE, "..", "configs",
                                        "solar-open2.json")))
    assert ssm_decode_roofline_pct.read(
        _obs(programs, solar, **counters)) is None
    del counters["recurrent_decode_rows"]
    assert ssm_decode_roofline_pct.read(_obs(programs, **counters)) is None
    assert ssm_decode_roofline_pct.read({"traced": None}) is None
    assert ssm_decode_roofline_pct.read({}) is None


def test_ssm_chunk_roofline_is_least_time_over_chunk_time():
    counters = dict(
        recurrent_chunk_tokens=9 * 20 * 460, recurrent_chunk_rows=9 * 20 * 512,
        moe_experts_hit=360 * 20 + 355, moe_decode_experts_hit=355,
        moe_expert_slots=360 * 21, moe_decode_expert_slots=360,
        moe_assignments=5 * 512 * 20 * 10 + 200, moe_decode_assignments=200,
        moe_rows_routed=10 * 512 * 20 * 10 + 400, moe_decode_rows_routed=400)
    programs = {"jit__prefill_chunk": {"count": 20, "total_s": 0.6}}
    got = ssm_chunk_roofline_pct.read(_obs(programs, **counters))
    assert got is not None and 0 < got < 100
    # every prompt 2,048 tokens in four chunks: the mean chunk starts at
    # 768; 460 real tokens of 512; all experts hit, half the picks held
    need = opsbytes_ssm.prefill_chunk(spec(), 768, 460, 1.0, 5.0)
    assert got == pytest.approx(100 * max(
        need["bytes"] / 819e9, need["flops"] / 197e12) / 30e-3, rel=0.02)
    assert ssm_chunk_roofline_pct.read(_obs(None, **counters)) is None
    del counters["recurrent_chunk_rows"]
    assert ssm_chunk_roofline_pct.read(_obs(programs, **counters)) is None
    assert ssm_chunk_roofline_pct.read({"traced": None}) is None
    assert ssm_chunk_roofline_pct.read({}) is None
