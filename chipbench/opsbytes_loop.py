"""Required operations and bytes of a LOOPED dense decoder (the
published ``total_ut_steps``: a token passes through the same
``num_hidden_layers`` layers that many times), from its configuration
file.  ``opsbytes.py``'s rules, with what the loop changes:

* every pass multiplies a token with every layer's matrices again:
  products and (query, key) score pairs count ``total_ut_steps`` times;
  the head once, behind the last pass; the exit gate (a ``hidden -> 1``
  product) once a pass;
* a decode step READS every layer's weights ``total_ut_steps`` times
  and the head once.  The re-read is REQUIRED, not a habit of the
  program: pass ``u + 1`` of layer 0 needs pass ``u`` of the LAST
  layer, so between two uses of one layer's matrices (102.8 MB of
  bfloat16 at Ouro-2.6B's widths) lie the other layers' — 4.8 GB —
  against an on-chip vector memory of 128 MiB on a v5e, of which a
  program is handed 16 MiB.  No order of a step's work that keeps to
  the dependency holds a layer's weights on the chip from one pass to
  the next.  (Rows of DIFFERENT steps in different passes at once — a
  pipeline over passes — would read a layer once for four passes' rows:
  that is another schedule with another latency, and this count is the
  yardstick it has to argue with.)
* the cache holds a slab layer for every (pass, layer) pair:
  ``total_ut_steps * num_hidden_layers`` of them.  A step reads the
  LIVE positions of all of them — what is read, not what is reserved —
  and writes one position of each;
* sandwich norms: four norm weights a layer (counted among the bytes;
  their operations, as every norm's, are not).
"""

from __future__ import annotations


def _dims(spec: dict):
    d, heads = spec["hidden_size"], spec["num_attention_heads"]
    head_dim = spec.get("head_dim") or d // heads
    return (d, heads, spec["num_key_value_heads"], head_dim,
            spec["intermediate_size"], spec["vocab_size"],
            spec["num_hidden_layers"], spec["total_ut_steps"])


def counts(spec: dict) -> dict:
    """Parameters: all of them, a layer's, those a token multiplies with
    over all its passes, and those a step reads."""
    d, h, kvh, hd, f, vocab, layers, passes = _dims(spec)
    matrices = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f
    a_layer = matrices + 4 * d
    closing = d + (d + 1)                 # the final norm, the exit gate
    return {
        "total": 2 * vocab * d + layers * a_layer + closing,
        "layer": a_layer,
        "slab_layers": passes * layers,
        "matmul_per_token": passes * (layers * matrices + d) + d * vocab,
        "matmul_per_token_no_head": passes * (layers * matrices + d),
        "weights_read_per_step": passes * (layers * a_layer + closing)
        + d * vocab,
    }


def _attn_flops(spec: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs, every pass of
    every layer."""
    _, h, _, hd, _, _, layers, passes = _dims(spec)
    return 2 * 2.0 * h * hd * pairs * layers * passes


def kv_bytes_per_position(spec: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of one position in every (pass, layer) pair's
    slab layer."""
    _, _, kvh, hd, _, _, layers, passes = _dims(spec)
    return 2 * kvh * hd * layers * passes * dtype_bytes


def decode_step(spec: dict, contexts: list, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step)."""
    c = counts(spec)
    pairs = sum(n + 1 for n in contexts)
    return {
        "flops": 2.0 * c["matmul_per_token"] * len(contexts)
        + _attn_flops(spec, pairs),
        "bytes": dtype_bytes * c["weights_read_per_step"]
        + kv_bytes_per_position(spec, dtype_bytes) * pairs
        + dtype_bytes * spec["hidden_size"] * len(contexts),
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
