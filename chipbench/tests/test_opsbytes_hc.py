"""``opsbytes_hc`` against a hand count at the published widths of
``xing4.0-29b-a4b`` (PR 56), and the two readers built on it on a
hand-made ``obs``."""

import json
import os

import pytest

from chipbench import opsbytes_hc, opsbytes_latent
from chipbench.layer_metrics import (
    hc_chunk_roofline_pct,
    hc_stream_required_pct,
)

HERE = os.path.dirname(os.path.abspath(__file__))
ATTENTION = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
EXPERT = 3 * 3584 * 1024
MAPS = 14336 * 24 + 24 + 3                       # a sub-layer
EMBED = 131072 * 3584
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def spec(name="xing4.0-29b-a4b"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_weights_counts_are_the_issues_arithmetic():
    c = opsbytes_latent.counts(spec())
    assert c["attention_matmul"] == ATTENTION == 28_409_856
    assert c["expert"] == EXPERT == 11_010_048
    assert (c["n_dense"], c["n_moe"], c["held"]) == (2, 5, 64)
    assert c["dense_mlp"] == 3 * 3584 * 9216
    assert opsbytes_hc.maps_params(spec()) == MAPS == 344_091
    # what the program holds (tests/test_xing4.py: 4,920,866,746) less
    # the maps' leaves and the router's bias
    assert c["total"] + 2 * 7 * MAPS + 5 * 64 == 4_920_866_746


def test_the_streams_passes_and_products_by_hand():
    s = spec()
    # 512 rows x 4 x 3584 values x 2 B = 14.7 MB a pass, three passes a
    # sub-layer, 14 sub-layers: 0.617 GB, 0.75 ms at the chip's bandwidth
    assert opsbytes_hc.stream_bytes(s, 512) == 3 * 14 * 512 * 4 * 3584 * 2 \
        == 616_562_688
    assert 0.74e-3 < opsbytes_hc.stream_bytes(s, 512) / 819e9 < 0.76e-3
    # a row a sub-layer: the maps 2 x 14,336 x 24, the read 2 x 4 x 3584,
    # the mix and the write 2 x 20 x 3584
    assert opsbytes_hc.stream_flops(s, 1) == 14 * (
        2 * 14336 * 24 + 2 * 4 * 3584 + 2 * 20 * 3584)
    assert opsbytes_hc.stream_flops(s, 512) < 0.01 * 0.8e12


def test_a_chunks_attention_takes_the_cheaper_form():
    s = spec()
    # a first chunk: nothing cached to expand, 320 a pair against 1,088
    first = opsbytes_hc.attention_flops(s, 0, 512)
    pairs = 512 * 513 / 2
    assert first == 7 * 2 * 32 * 320 * pairs
    # far into a prompt the per-head form still wins: expanding a cached
    # position costs 512 x 256 a head once, a pair saves 768 a head
    deep = opsbytes_hc.attention_flops(s, 8192, 512)
    pairs = 512 * 8192 + 512 * 513 / 2
    assert deep == 7 * 2 * 32 * (320 * pairs + 512 * 256 * 8192)
    assert deep < opsbytes_latent.absorbed_attention_flops(s, pairs)
    # one row over a long context is the decode step's case: absorbed
    assert opsbytes_hc.attention_flops(s, 8192, 1) == \
        opsbytes_latent.absorbed_attention_flops(s, 8193)


def test_a_whole_chunk_by_hand_is_bound_by_the_weights_bytes():
    s = spec()
    need = opsbytes_hc.prefill_chunk(s, 2048, 512, 1.0)
    held = 4_920_866_746 - 5 * 64 - EMBED        # all but the embedding
    assert need["bytes"] == 2 * held + 7 * 576 * 2 * (2048 + 1024) \
        + 2 * 3584 * 512 + 616_562_688
    per_token = 7 * ATTENTION + 2 * 3 * 3584 * 9216 \
        + 5 * (EXPERT + 3584 * 64 + 4 * EXPERT)
    assert need["flops"] == 2 * per_token * 512 + 2 * EMBED \
        + opsbytes_hc.attention_flops(s, 2048, 512) \
        + opsbytes_hc.stream_flops(s, 512)
    # 0.69 TFLOP of weights' products + 0.29 of attention at 2,048
    # cached positions: 5.0 ms at the bf16 peak; 9.55 GB at the
    # bandwidth: 11.7 ms
    assert 4.9e-3 < need["flops"] / 197e12 < 5.1e-3
    assert 11.5e-3 < need["bytes"] / 819e9 < 11.8e-3
    least, streams = opsbytes_hc.least_seconds(need, PEAKS)
    assert least == need["bytes"] / 819e9
    assert streams == 616_562_688 / 819e9
    # with half the experts hit, half their bytes
    half = opsbytes_hc.prefill_chunk(s, 2048, 512, 0.5)
    assert need["bytes"] - half["bytes"] == 2 * 5 * 32 * EXPERT
    # where operations bound (a chip with ten times the bandwidth), the
    # streams' part is their operations'
    fast = {**PEAKS, "hbm_bytes_per_s": 8190e9}
    least, streams = opsbytes_hc.least_seconds(need, fast)
    assert least == need["flops"] / 197e12
    assert streams == need["stream_flops"] / 197e12


def _obs(**engine_after):
    before = {"hc_chunk_rows": 1000, "chunks": 10, "moe_experts_hit": 5000,
              "moe_decode_experts_hit": 3000, "moe_expert_slots": 8000,
              "moe_decode_expert_slots": 4800}
    after = {"hc_chunk_rows": 1000 + 20 * 480, "chunks": 30,
             "moe_experts_hit": 5000 + 20 * 320 + 900,
             "moe_decode_experts_hit": 3000 + 900,
             "moe_expert_slots": 8000 + 20 * 320 + 1600,
             "moe_decode_expert_slots": 4800 + 1600, **engine_after}
    return {
        "config": spec(), "peaks": PEAKS,
        "trace": {"programs": {"jit__prefill_chunk": {
            "count": 20, "seconds": 20 * 0.0205}}},
        "traced": {"engine": after, "engine_before": before,
                   "chunk_width": 512},
        "client": {"requests": [(4096, 0.0), (1024, 1.0)]},
    }


def test_the_readers_on_a_hand_made_window(monkeypatch):
    monkeypatch.setattr(hc_chunk_roofline_pct.prefill_chunk_ms, "read",
                        lambda obs: 20.5)
    obs = _obs()
    # 480 real rows a chunk, every expert hit by the chunks; starts of
    # the log's prompts: 0 .. 3584 and 0, 512 -> mean 1,484.8
    need = opsbytes_hc.prefill_chunk(spec(), 1484.8, 480, 1.0)
    least, streams = opsbytes_hc.least_seconds(need, PEAKS)
    assert hc_chunk_roofline_pct.read(obs) == pytest.approx(
        100 * least / 0.0205)
    assert 50 < hc_chunk_roofline_pct.read(obs) < 60
    assert hc_stream_required_pct.read(obs) == pytest.approx(
        100 * streams / least)
    assert 5 < hc_stream_required_pct.read(obs) < 8


@pytest.mark.parametrize("without", ["hc_chunk_rows", "hc_mult",
                                     "moe_experts_hit"])
def test_the_readers_are_silent_without_the_counter_or_the_key(
        monkeypatch, without):
    """Another program (the parent's: no ``hc_chunk_rows``), another
    configuration (no ``hc_mult``), a dense model (no routing counters):
    nothing, and no exception."""
    monkeypatch.setattr(hc_chunk_roofline_pct.prefill_chunk_ms, "read",
                        lambda obs: 20.5)
    obs = _obs()
    obs["config"].pop(without, None)
    for side in ("engine", "engine_before"):
        obs["traced"][side].pop(without, None)
    assert hc_chunk_roofline_pct.read(obs) is None
    assert hc_stream_required_pct.read(obs) is None
    # and with no trace at all
    assert hc_chunk_roofline_pct.read({"config": spec()}) is None
    assert hc_stream_required_pct.read({"config": spec()}) is None
