"""``opsbytes`` against numbers worked by hand for both configurations."""

import json
import os

import pytest

from chipbench import opsbytes

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_internlm2_parameters_and_train_operations():
    spec = config("internlm2-1.8b")
    c = opsbytes.counts(spec)
    # embed + head: 2 x 92,544 x 2,048; a layer: q 2048x2048, k and v
    # 2048x1024 each, o 2048x2048, three 2048x8192, two norms.
    layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 2 * 2048
    assert c["total"] == 2 * 92544 * 2048 + 24 * layer + 2048 == 1_889_110_016
    matmul = 24 * (layer - 2 * 2048) + 2048 * 92544      # no embedding
    assert c["matmul_per_token"] == matmul == 1_699_479_552
    # 6 x matmul parameters + causal attention once: forward 2 matmuls
    # of 2 ops over (s + 1) / 2 keys x 16 heads x 128, x 24 layers, x 3.
    attention = 3 * 4 * 16 * 128 * (4097 / 2) * 24
    assert opsbytes.train_flops_per_token(spec, 4096) == pytest.approx(
        6 * matmul + attention)
    assert opsbytes.train_flops_per_token(spec, 4096) / 1e9 == \
        pytest.approx(11.4, abs=0.05)


def test_mistral_parameters_at_the_reduced_depth():
    spec = config("mistral-7b")
    assert spec["num_hidden_layers"] == 16
    c = opsbytes.counts(spec)
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert c["total"] == 2 * 32768 * 4096 + 16 * layer + 4096 == 3_758_231_552
    assert c["total"] * 2 / 1e9 == pytest.approx(7.52, abs=0.01)   # bf16 GB


def test_decode_step_reads_weights_once_and_the_valid_cache_only():
    spec = config("mistral-7b")
    c = opsbytes.counts(spec)
    one = opsbytes.decode_step(spec, [0])
    full = opsbytes.decode_step(spec, [599] * 16)
    per_position = 2 * 8 * 128 * 16 * 2                  # k, v, bf16
    assert opsbytes.kv_bytes_per_position(spec) == per_position == 65_536
    weights = 2 * (c["total"] - c["embed"])
    assert one["bytes"] == weights + per_position + 2 * 4096
    assert full["bytes"] == weights + 16 * 600 * per_position + 16 * 2 * 4096
    # the batch multiplies the operations, not the weight bytes
    assert full["flops"] == pytest.approx(
        16 * 2 * c["matmul_per_token"]
        + 16 * 600 * 4 * 32 * 128 * 16)
    assert weights / 819e9 == pytest.approx(9.0e-3, rel=0.02)   # >= 9 ms


def test_prefill_chunk_is_bandwidth_bound_at_64_tokens():
    spec = config("mistral-7b")
    need = opsbytes.prefill_chunk(spec, 512, 64)
    c = opsbytes.counts(spec)
    pairs = 64 * 512 + 64 * 65 / 2
    assert need["flops"] == pytest.approx(
        2 * c["matmul_per_token_no_head"] * 64 + 2 * 4096 * 32768
        + 4 * 32 * 128 * pairs * 16)
    assert need["flops"] / 197e12 < need["bytes"] / 819e9


def test_only_the_routed_experts_count():
    dense = config("mistral-7b")
    moe = dict(dense, num_local_experts=8, num_experts_per_tok=2)
    d, m = opsbytes.counts(dense), opsbytes.counts(moe)
    mlp = 3 * 4096 * 14336
    assert m["total"] - d["total"] == 16 * (7 * mlp + 4096 * 8)
    assert m["matmul_per_token"] - d["matmul_per_token"] == \
        16 * (mlp + 4096 * 8)
