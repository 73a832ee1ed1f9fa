"""``opsbytes_conv`` against the arithmetic of the issue that added
``lfm2-8b-a1b`` (PR 65) and hand-reckoned bytes of one step, and the two
readers built on it on a hand-made ``obs``."""

import json
import os

import pytest

from chipbench import opsbytes_conv
from chipbench.layer_metrics import (
    conv_chunk_roofline_pct,
    conv_decode_roofline_pct,
)

HERE = os.path.dirname(os.path.abspath(__file__))
EXPERT, TOTAL, EMBED = 11_010_048, 4_667_077_376, 134_217_728
ROUTED = 12 * 32 * EXPERT                        # every expert held
A_TAIL = 2 * 2 * 2048                            # a slot-layer, one way, B
POSITION = 3 * 2 * 8 * 64 * 2                    # three softmax layers: 6 KiB


def spec():
    with open(os.path.join(HERE, "..", "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_counts_are_the_issues_arithmetic():
    c = opsbytes_conv.counts(spec())
    assert opsbytes_conv.layer_kinds(spec()) == (11, 3)
    assert c["conv_matmul"] + c["conv_taps"] == 16_783_360
    assert c["softmax_matmul"] + 128 == 10_485_888
    assert c["expert"] == EXPERT and c["dense"] == 44_040_192
    assert c["router"] == 65_536 and c["experts"] == 32
    assert (c["n_dense"], c["n_routed"]) == (2, 12)
    assert c["total"] == TOTAL                   # 8.69 GiB of bfloat16
    assert c["outside_experts"] == TOTAL - ROUTED - EMBED - 2048
    assert opsbytes_conv.tail_values(spec()) == 2 * 2048
    assert opsbytes_conv.position_bytes(spec()) == POSITION == 6_144
    assert 11 * 2 * opsbytes_conv.tail_values(spec()) == 90_112   # B a slot
    assert opsbytes_conv.head_dim(spec()) == 64


def test_a_decode_step_counts_the_rows_decoded_and_what_is_read():
    s = spec()
    assert opsbytes_conv.tail_bytes(s, 1) == 11 * 2 * A_TAIL
    assert opsbytes_conv.cache_bytes(s, [399, 999]) == 1400 * POSITION
    every = opsbytes_conv.decode_step(s, [999, 399], 1.0)
    none = opsbytes_conv.decode_step(s, [999, 399], 0.0)
    assert every["expert_bytes"] == 2 * ROUTED
    assert every["bytes"] - none["bytes"] == every["expert_bytes"]
    # the weights outside the experts once — the tied embedding as the
    # head once, of it as the embedding two rows —, the tails of the two
    # rows decoded, the softmax layers' live positions
    assert none["bytes"] == 2 * (TOTAL - ROUTED) + 2 * 11 * 2 * A_TAIL \
        + 1400 * POSITION + 2 * 2048 * 2
    # ninety-six slots' tails are not what two rows need
    assert every["tail_bytes"] == 2 * 11 * 2 * A_TAIL
    # the cell's step: ~60 rows at ~500 positions, ~0.9 of the experts
    # hit: the weights are nearly all that must move (the tails a
    # thousandth, the slabs a fiftieth), and bytes bound it
    full = opsbytes_conv.decode_step(s, [500] * 60, 0.9)
    assert full["tail_bytes"] / full["bytes"] < 0.002
    assert 0.01 < full["cache_bytes"] / full["bytes"] < 0.03
    assert full["bytes"] / 819e9 > 5 * full["flops"] / 197e12
    assert 0.0095 < full["bytes"] / 819e9 < 0.0115
    # a token: the mixers, 3 taps a channel in eleven layers, two dense
    # SwiGLUs, twelve routers and 4 experts each, the head
    one = opsbytes_conv.decode_step(s, [0], 1.0)
    c = opsbytes_conv.counts(s)
    assert one["flops"] == 2.0 * (
        11 * 16_783_360 + 3 * c["softmax_matmul"] + 2 * c["dense"]
        + 12 * (c["router"] + 4 * EXPERT) + EMBED) + 2 * 2 * 32 * 64 * 3


def test_a_chunk_counts_one_slots_tail_its_real_tokens_and_the_weights_once():
    s = spec()
    whole = opsbytes_conv.prefill_chunk(s, 512, 512, 1.0)
    assert whole["bytes"] == 2 * TOTAL + 11 * 2 * A_TAIL + (
        512 + 2 * 512) * POSITION + 2 * 2048 * 512
    assert whole["tail_bytes"] == 11 * 2 * A_TAIL
    fewer = opsbytes_conv.prefill_chunk(s, 512, 512, 0.5)
    assert whole["bytes"] - fewer["bytes"] == ROUTED
    # padding is not what the algorithm needs: 200 real tokens of 512
    short = opsbytes_conv.prefill_chunk(s, 0, 200, 1.0)
    c = opsbytes_conv.counts(s)
    per_token = (11 * 16_783_360 + 3 * c["softmax_matmul"] + 2 * c["dense"]
                 + 12 * (c["router"] + 4 * EXPERT))
    assert short["flops"] == 2.0 * per_token * 200 + 2.0 * EMBED \
        + 2 * 2 * 32 * 64 * 3 * (200 * 201 / 2)
    # bound by the weights' bytes at the chunks the cell has
    assert short["bytes"] / 819e9 > short["flops"] / 197e12
    assert whole["bytes"] / 819e9 > whole["flops"] / 197e12


def _obs(programs=None, config=None, **engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 512},
            "window_wall": 1000.0, "config": config or spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(400, [10.0 + 0.03 * i
                                           for i in range(400)])] * 60},
            "trace": programs and {"devices": [{"programs": programs}]}}


def test_conv_decode_roofline_is_least_time_over_step_time():
    counters = dict(moe_decode_experts_hit=350, moe_decode_expert_slots=384,
                    recurrent_decode_rows=660)
    programs = {"jit__decode": {"count": 100, "total_s": 1.6},
                "jit__sample_batch": {"count": 100, "total_s": 0.05}}
    got = conv_decode_roofline_pct.read(_obs(programs, **counters))
    # sixty rows at contexts of about 600: what must be moved at
    # 819 GB/s over the 16.5 ms a step and its sampler took
    need = opsbytes_conv.decode_step(spec(), [600] * 60, 350 / 384)
    assert got == pytest.approx(
        100 * need["bytes"] / 819e9 / 16.5e-3, rel=0.02)
    assert 0 < got < 100
    # no device trace -> no step time -> nothing; a program without the
    # counters, or another family's configuration: nothing, no raise
    assert conv_decode_roofline_pct.read(_obs(None, **counters)) is None
    granite = json.load(open(os.path.join(
        HERE, "..", "configs", "granite-4.0-h-small.json")))
    assert conv_decode_roofline_pct.read(
        _obs(programs, granite, **counters)) is None
    for gone in counters:
        assert conv_decode_roofline_pct.read(_obs(programs, **{
            k: v for k, v in counters.items() if k != gone})) is None
    assert conv_decode_roofline_pct.read({"traced": None}) is None
    assert conv_decode_roofline_pct.read({}) is None


def test_conv_chunk_roofline_is_least_time_over_chunk_time():
    counters = dict(
        recurrent_chunk_tokens=11 * 50 * 300, recurrent_chunk_rows=11 * 50 * 512,
        moe_experts_hit=384 * 50 + 350, moe_decode_experts_hit=350,
        moe_expert_slots=384 * 51, moe_decode_expert_slots=384)
    programs = {"jit__prefill_chunk": {"count": 50, "total_s": 0.75}}
    got = conv_chunk_roofline_pct.read(_obs(programs, **counters))
    # every prompt 400 tokens in one chunk: the mean chunk starts at 0;
    # 300 real tokens of 512; all experts hit
    need = opsbytes_conv.prefill_chunk(spec(), 0, 300, 1.0)
    assert got == pytest.approx(100 * max(
        need["bytes"] / 819e9, need["flops"] / 197e12) / 15e-3, rel=0.02)
    assert 0 < got < 100
    assert conv_chunk_roofline_pct.read(_obs(None, **counters)) is None
    solar = json.load(open(os.path.join(HERE, "..", "configs",
                                        "solar-open2.json")))
    assert conv_chunk_roofline_pct.read(
        _obs(programs, solar, **counters)) is None
    for gone in counters:
        assert conv_chunk_roofline_pct.read(_obs(programs, **{
            k: v for k, v in counters.items() if k != gone})) is None
    assert conv_chunk_roofline_pct.read(_obs(programs, **{
        **counters, "recurrent_chunk_rows": 0})) is None
    assert conv_chunk_roofline_pct.read({"traced": None}) is None
    assert conv_chunk_roofline_pct.read({}) is None
