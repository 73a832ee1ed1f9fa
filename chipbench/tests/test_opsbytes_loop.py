"""``opsbytes_loop`` against the arithmetic of the issue that added
``ouro-2.6b`` (PR 50), and the two readers built beside it on a
hand-made ``obs``."""

import json
import os

import pytest

from chipbench import opsbytes, opsbytes_loop
from chipbench.layer_metrics import (
    loop_decode_roofline_pct,
    loop_exit_pass_mean,
)

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER, TOTAL, EMBED = 51_388_416, 2_667_974_657, 100_663_296
POSITION = 192 * 2 * 16 * 128 * 2               # 1.5 MiB


def spec(name="ouro-2.6b"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_counts_are_the_issues_arithmetic():
    c = opsbytes_loop.counts(spec())
    assert c["layer"] == LAYER == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert c["total"] == TOTAL == 48 * LAYER + 2 * EMBED + 2048 + 2049
    assert c["slab_layers"] == 192
    assert opsbytes_loop.kv_bytes_per_position(spec()) == POSITION \
        == 1_572_864
    # a step reads the layers four times and the head once: 19.9 GB
    read = 2 * c["weights_read_per_step"]
    assert read == 2 * (4 * (48 * LAYER + 2048 + 2049) + EMBED)
    assert 19.9e9 < read < 20.0e9
    assert 0.0243 < read / 819e9 < 0.0245
    # what the one-pass count charges of it: a quarter and the head
    once = 2 * opsbytes.counts(spec())["weights_read_per_step"]
    assert 0.25 < once / read < 0.27
    assert opsbytes.kv_bytes_per_position(spec()) * 4 == POSITION


def test_a_decode_step_reads_the_live_positions_of_every_slab_layer():
    s, c = spec(), opsbytes_loop.counts(spec())
    two = opsbytes_loop.decode_step(s, [99, 299])
    assert two["bytes"] == 2 * c["weights_read_per_step"] \
        + 400 * POSITION + 2 * 2048 * 2
    matrices = LAYER - 4 * 2048
    assert two["flops"] == 2.0 * 2 * (4 * (48 * matrices + 2048) + EMBED) \
        + 2 * 2.0 * 16 * 128 * 400 * 192
    # the cell's step: eight rows at ~400 positions - 5 GB of slabs
    # beside 19.9 GB of weights; bytes bound it
    eight = opsbytes_loop.decode_step(s, [400] * 8)
    slabs = 8 * 401 * POSITION
    assert 5.0e9 < slabs < 5.1e9
    assert eight["bytes"] / 819e9 > 20 * eight["flops"] / 197e12
    assert 0.030 < eight["bytes"] / 819e9 < 0.031


def test_a_chunk_counts_every_pass():
    s, c = spec(), opsbytes_loop.counts(spec())
    one = opsbytes_loop.prefill_chunk(s, 0, 1)
    assert one["flops"] == 2.0 * c["matmul_per_token_no_head"] \
        + 2.0 * EMBED + 2 * 2.0 * 16 * 128 * 192
    whole = opsbytes_loop.prefill_chunk(s, 64, 64)
    assert whole["bytes"] == 2 * c["weights_read_per_step"] + 128 * POSITION
    # 64 tokens: 1.3 TFLOP, 6.4 ms at the peak, under 24.5 ms of
    # weight bytes - a riding chunk is bound by bytes too
    assert whole["flops"] / 197e12 < whole["bytes"] / 819e9


def _obs(programs=None, config=None, **engine):
    before = dict.fromkeys(engine, 0)
    return {"traced": {"engine": engine, "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 64},
            "window_wall": 1000.0, "config": config or spec(),
            "peaks": {"hbm_bytes_per_s": 819e9,
                      "bf16_flops_per_s": 197e12},
            "client": {"requests": [(128, [10.0 + 0.035 * i
                                           for i in range(400)])] * 8},
            "trace": programs and {"devices": [{"programs": programs}]}}


def test_loop_decode_roofline_is_least_time_over_step_time():
    programs = {"jit__decode": {"count": 100, "total_s": 3.4},
                "jit__sample_batch": {"count": 100, "total_s": 0.05}}
    got = loop_decode_roofline_pct.read(_obs(programs, loop_passes=400))
    # eight rows at contexts of about 370
    need = opsbytes_loop.decode_step(spec(), [370] * 8)
    assert got == pytest.approx(
        100 * need["bytes"] / 819e9 / 34.5e-3, rel=0.02)
    assert 0 < got < 100
    # no device trace -> no step time -> nothing; a program without the
    # counter (the parent's), a configuration without the key: nothing,
    # and no error
    assert loop_decode_roofline_pct.read(_obs(None, loop_passes=400)) is None
    assert loop_decode_roofline_pct.read(_obs(programs)) is None
    assert loop_decode_roofline_pct.read(
        _obs(programs, spec("mistral-7b"), loop_passes=400)) is None
    assert loop_decode_roofline_pct.read(
        _obs(programs, spec("granite-4.0-h-small"))) is None
    assert loop_decode_roofline_pct.read({"traced": None}) is None
    assert loop_decode_roofline_pct.read({}) is None


def test_loop_exit_pass_mean_is_the_sum_over_the_rows():
    got = loop_exit_pass_mean.read(_obs(exit_pass_sum=1520.0, exit_rows=800))
    assert got == pytest.approx(1.9)
    assert loop_exit_pass_mean.read(
        _obs(exit_pass_sum=0.0, exit_rows=0)) is None
    assert loop_exit_pass_mean.read(_obs(exit_rows=800)) is None
    assert loop_exit_pass_mean.read(_obs()) is None
    assert loop_exit_pass_mean.read({"traced": None}) is None
    assert loop_exit_pass_mean.read({}) is None
