"""``opsbytes_gdn`` against the arithmetic of the issue that added
``olmo-hybrid-7b`` (PR 46), and the two readers built on it on a
hand-made ``obs``."""

import json
import os

import pytest

from chipbench import opsbytes_gdn
from chipbench.layer_metrics import (
    gdn_chunk_roofline_pct,
    gdn_decode_roofline_pct,
)

HERE = os.path.dirname(os.path.abspath(__file__))
TOTAL, LAYERS, EMBED = 4_100_788_944, 3_330_081_744, 385_351_680
A_STATE = 4 * 30 * 96 * 192 + 2 * 3 * 11520     # a slot-layer, one way
POSITION = 4 * 2 * 30 * 128 * 2                 # FOUR softmax layers: 60 KiB


def spec(name="olmo-hybrid-7b"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_counts_are_the_issues_arithmetic():
    c = opsbytes_gdn.counts(spec())
    assert c["linear_matmul"] == 2 * 3840 * 2880 + 3 * 3840 * 5760 \
        + 2 * 3840 * 30
    assert c["softmax_matmul"] == 4 * 3840 * 3840
    assert c["mlp"] == 126_812_160
    assert c["layers"] == LAYERS and c["embed"] == c["head"] == EMBED
    assert c["total"] == TOTAL                   # 7.64 GiB of bfloat16
    assert opsbytes_gdn.layer_kinds(spec()) == (12, 4)
    assert opsbytes_gdn.state_values(spec()) == 30 * 96 * 192
    assert opsbytes_gdn.conv_tail_values(spec()) == 3 * 11520
    assert opsbytes_gdn.position_bytes(spec()) == POSITION == 61_440


def test_a_decode_step_counts_the_rows_decoded_and_what_is_read():
    s = spec()
    assert opsbytes_gdn.state_bytes(s, 1) == 12 * 2 * A_STATE
    assert opsbytes_gdn.cache_bytes(s, [999, 2499]) == 3500 * POSITION
    two = opsbytes_gdn.decode_step(s, [2499, 999])
    # every layer's weights, the final norm and the whole head once —
    # of the embedding two rows —, the state of the two rows decoded,
    # the softmax layers' live positions
    assert two["bytes"] == 2 * (LAYERS + EMBED + 3840) + 2 * 12 * 2 \
        * A_STATE + 3500 * POSITION + 2 * 3840 * 2
    # eight slots' states are not what two rows need
    assert two["state_bytes"] == 2 * 12 * 2 * A_STATE
    # the cell's step: three rows at ~4k positions; the slabs' live
    # positions outweigh the state, and bytes bound it, not operations
    three = opsbytes_gdn.decode_step(s, [4000] * 3)
    assert 4 < three["cache_bytes"] / three["state_bytes"] < 5
    assert three["bytes"] / 819e9 > 50 * three["flops"] / 197e12
    assert 0.0095 < three["bytes"] / 819e9 < 0.0105
    # from 446 positions on a slot's slabs outweigh its state
    assert 445 * POSITION < 12 * A_STATE < 451 * POSITION


def test_a_chunk_counts_one_slots_state_and_is_compute_bound():
    s, c = spec(), opsbytes_gdn.counts(spec())
    whole = opsbytes_gdn.prefill_chunk(s, 1024, 512)
    assert whole["bytes"] == 2 * (LAYERS + EMBED + 3840) + 12 * 2 * A_STATE \
        + (1024 + 2 * 512) * POSITION + 2 * 3840 * 512
    assert whole["state_bytes"] == 12 * 2 * A_STATE
    # a token: the weights' products, the three products with the state
    # in each linear layer, its pair in each softmax layer; the head once
    one = opsbytes_gdn.prefill_chunk(s, 0, 1)
    assert one["flops"] == 2.0 * (
        12 * c["linear_matmul"] + 4 * c["softmax_matmul"] + 16 * c["mlp"]
        + c["head"]) + 12 * 3 * 2 * 30 * 96 * 192 + 4 * 2 * 2 * 3840
    # the issue's reckoning: 3.4 TFLOP, 17 ms at the peak, against 10 ms
    # of weight bytes — the benchmark's first compute-bound hybrid chunk
    assert 3.3e12 < whole["flops"] < 3.6e12
    assert 0.0165 < whole["flops"] / 197e12 < 0.0185
    assert 0.009 < whole["bytes"] / 819e9 < 0.0105


def _obs(programs=None, config=None, **engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 512},
            "window_wall": 1000.0, "config": config or spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(4096, [10.0 + 0.03 * i
                                            for i in range(400)])] * 3},
            "trace": programs and {"devices": [{"programs": programs}]}}


def test_gdn_decode_roofline_is_least_time_over_step_time():
    counters = dict(recurrent_decode_rows=36)
    programs = {"jit__decode": {"count": 100, "total_s": 1.3},
                "jit__sample_batch": {"count": 100, "total_s": 0.05}}
    got = gdn_decode_roofline_pct.read(_obs(programs, **counters))
    # three rows at contexts of about 4,300: what must be moved at
    # 819 GB/s over the 13.5 ms a step took
    need = opsbytes_gdn.decode_step(spec(), [4300] * 3)
    assert got == pytest.approx(
        100 * need["bytes"] / 819e9 / 13.5e-3, rel=0.02)
    assert 0 < got < 100
    # no device trace -> no step time -> nothing; a program without the
    # recurrent counters, or another family's configuration: nothing
    assert gdn_decode_roofline_pct.read(_obs(None, **counters)) is None
    assert gdn_decode_roofline_pct.read(
        _obs(programs, spec("solar-open2"), **counters)) is None
    assert gdn_decode_roofline_pct.read(_obs(programs)) is None
    assert gdn_decode_roofline_pct.read({"traced": None}) is None
    assert gdn_decode_roofline_pct.read({}) is None


def test_gdn_chunk_roofline_is_least_time_over_chunk_time():
    counters = dict(recurrent_chunk_tokens=12 * 20 * 460,
                    recurrent_chunk_rows=12 * 20 * 512)
    programs = {"jit__prefill_chunk": {"count": 20, "total_s": 0.6}}
    got = gdn_chunk_roofline_pct.read(_obs(programs, **counters))
    assert got is not None and 0 < got < 100
    # every prompt 4,096 tokens in eight chunks: the mean chunk starts
    # at 1,792; 460 real tokens of 512; operations bound it
    need = opsbytes_gdn.prefill_chunk(spec(), 1792, 460)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9
    assert got == pytest.approx(
        100 * need["flops"] / 197e12 / 30e-3, rel=0.02)
    assert gdn_chunk_roofline_pct.read(_obs(None, **counters)) is None
    assert gdn_chunk_roofline_pct.read(
        _obs(programs, spec("granite-4.0-h-small"), **counters)) is None
    del counters["recurrent_chunk_rows"]
    assert gdn_chunk_roofline_pct.read(_obs(programs, **counters)) is None
    assert gdn_chunk_roofline_pct.read({"traced": None}) is None
    assert gdn_chunk_roofline_pct.read({}) is None
