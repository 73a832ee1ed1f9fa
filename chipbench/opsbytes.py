"""Required operations and bytes, from a configuration file.  What the
ALGORITHM needs, not what a program happens to execute:

* a multiply-add is 2 operations;
* the embedding lookup is a gather, not a matmul: no operations, and
  bytes only for the rows read;
* causal attention is counted once: a token attends to the tokens up to
  itself, so a sequence of s tokens costs s * (s + 1) / 2 pairs, not s^2;
* recomputation (remat) is not counted: training is 3x the forward
  matmul operations (forward, and two matmuls per matmul backward);
* a mixture of experts counts the experts a token is routed to only
  (``num_experts_per_tok`` of ``num_local_experts``), plus the router;
* norms, rotary embedding, softmax and the optimizer are not counted
  (a few operations per element beside thousands);
* decode reads every weight once per step whatever the batch, and the
  VALID part of the cache of the active contexts only.
"""

from __future__ import annotations


def _dims(spec: dict):
    d, heads = spec["hidden_size"], spec["num_attention_heads"]
    head_dim = spec.get("head_dim") or d // heads
    return (d, heads, spec["num_key_value_heads"], head_dim,
            spec["intermediate_size"], spec["vocab_size"],
            spec["num_hidden_layers"])


def counts(spec: dict) -> dict:
    """Parameters: all of them, and those a token multiplies with."""
    d, h, kvh, hd, f, vocab, layers = _dims(spec)
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    experts = spec.get("num_local_experts") or 0
    if experts:
        mlp_all = experts * 3 * d * f + d * experts
        mlp_active = spec["num_experts_per_tok"] * 3 * d * f + d * experts
    else:
        mlp_all = mlp_active = 3 * d * f
    head = 0 if spec.get("tie_word_embeddings") else d * vocab
    embed = vocab * d
    return {
        "total": embed + head + layers * (attn + mlp_all + 2 * d) + d,
        "matmul_per_token": layers * (attn + mlp_active) + d * vocab,
        "matmul_per_token_no_head": layers * (attn + mlp_active),
        "weights_read_per_step": layers * (attn + mlp_all + 2 * d) + d
        + d * vocab,
        "embed": embed,
    }


def _attn_flops(spec: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
    _, h, _, hd, _, _, layers = _dims(spec)
    return 2 * 2.0 * h * hd * pairs * layers


def kv_bytes_per_position(spec: dict, dtype_bytes: int = 2) -> int:
    _, _, kvh, hd, _, _, layers = _dims(spec)
    return 2 * kvh * hd * layers * dtype_bytes


def train_flops_per_token(spec: dict, seq: int) -> float:
    c = counts(spec)
    forward = 2.0 * c["matmul_per_token"] + _attn_flops(
        spec, seq * (seq + 1) / 2) / seq
    return 3.0 * forward


def decode_step(spec: dict, contexts: list, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step)."""
    c = counts(spec)
    pairs = sum(n + 1 for n in contexts)
    per_pos = kv_bytes_per_position(spec, dtype_bytes)
    return {
        "flops": 2.0 * c["matmul_per_token"] * len(contexts)
        + _attn_flops(spec, pairs),
        "bytes": dtype_bytes * c["weights_read_per_step"]
        + per_pos * pairs + dtype_bytes * spec["hidden_size"] * len(contexts),
    }


def prefill_chunk(spec: dict, start: float, tokens: float,
                  dtype_bytes: int = 2) -> dict:
    """``tokens`` prompt tokens at positions start .. start + tokens - 1
    of one sequence; logits for one position."""
    c = counts(spec)
    pairs = tokens * start + tokens * (tokens + 1) / 2
    d, vocab = spec["hidden_size"], spec["vocab_size"]
    return {
        "flops": 2.0 * c["matmul_per_token_no_head"] * tokens
        + 2.0 * d * vocab + _attn_flops(spec, pairs),
        "bytes": dtype_bytes * c["weights_read_per_step"]
        + kv_bytes_per_position(spec, dtype_bytes) * (start + tokens),
    }
