"""Required operations and bytes of a DENSE decoder whose layers are
gated delta-rule layers with ONE decay a head over a rectangular state
(``linear_attention``) and multi-head softmax layers mixed
(``configs/olmo-hybrid-7b.json``), from its configuration file; the
rules are ``opsbytes.py``'s — what the algorithm needs, a multiply-add
is 2 operations, the embedding is a gather, norms, decays, gates,
convolutions and the softmax are not counted — with what is this
family's own:

* a LINEAR layer keeps nothing of a position: of a sequence it keeps
  the state, ``linear_num_value_heads * linear_key_head_dim *
  linear_value_head_dim`` float32 VALUES (what a device pads them to is
  not required), and the last ``linear_conv_kernel_dim - 1`` inputs of
  the convolution over q, k and v (2 d_k + d_v a head).  A decode step
  reads and writes both ONCE for every row it decodes — not for the
  slots that sit the step out — and multiplies the state three times a
  row: with the key, with the write, with the query;
* a SOFTMAX layer keeps ``2 * num_key_value_heads * head_dim`` values a
  position — 30 heads, no grouping: 15,360 B a layer — and a decode
  step reads every live position of a context: what is READ, not what
  a slab reserves;
* every weight is read once a program: the layers', the final norm and
  the whole untied head; of the embedding its rows only;
* a prefill chunk is ONE sequence's: it reads and writes that slot's
  state and tails once in every linear layer, multiplies the state
  three times a token as a step does (the block form's pair products
  and its triangular solve are how a program does it, not what the
  algorithm needs), reads the softmax layers' live positions once and
  writes its own; logits for one position.

Of ``layer_types`` the first ``num_hidden_layers`` are run.
"""

from __future__ import annotations

STATE_BYTES = 4          # the state is float32 whatever the weights are


def layer_kinds(spec: dict) -> tuple:
    """(linear layers, softmax layers) of the layers run."""
    run = spec["layer_types"][:spec["num_hidden_layers"]]
    n_linear = sum(kind == "linear_attention" for kind in run)
    return n_linear, len(run) - n_linear


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads, kvh = (spec["hidden_size"], spec["num_attention_heads"],
                     spec["num_key_value_heads"])
    hd = d // heads
    lin_heads, d_k, d_v = (spec["linear_num_value_heads"],
                           spec["linear_key_head_dim"],
                           spec["linear_value_head_dim"])
    # wq, wo; wk, wv
    softmax_matmul = 2 * d * heads * hd + 2 * d * kvh * hd
    softmax_small = heads * hd + kvh * hd                  # the QK norms
    # wq, wk; wv, the output gate, wo; the decay's and the write's
    linear_matmul = (2 * d * lin_heads * d_k + 3 * d * lin_heads * d_v
                     + 2 * d * lin_heads)
    # the taps, A_log and dt_bias, the norm a head
    linear_small = (spec["linear_conv_kernel_dim"] * lin_heads
                    * (2 * d_k + d_v) + 2 * lin_heads + d_v)
    mlp = 3 * d * spec["intermediate_size"]
    n_linear, n_softmax = layer_kinds(spec)
    embed = spec["vocab_size"] * d
    layers = (n_linear * (linear_matmul + linear_small + mlp + 2 * d)
              + n_softmax * (softmax_matmul + softmax_small + mlp + 2 * d))
    return {"softmax_matmul": softmax_matmul,
            "linear_matmul": linear_matmul, "mlp": mlp,
            "n_linear": n_linear, "n_softmax": n_softmax,
            "embed": embed, "head": embed, "layers": layers,
            "total": layers + 2 * embed + d}


def state_values(spec: dict) -> int:
    """float32 values of one slot's state in one linear layer."""
    return (spec["linear_num_value_heads"] * spec["linear_key_head_dim"]
            * spec["linear_value_head_dim"])


def conv_tail_values(spec: dict) -> int:
    """Values of one slot's convolution tails in one linear layer."""
    return (spec["linear_conv_kernel_dim"] - 1) \
        * spec["linear_num_value_heads"] * (
            2 * spec["linear_key_head_dim"] + spec["linear_value_head_dim"])


def state_bytes(spec: dict, rows: int, dtype_bytes: int = 2) -> float:
    """What a program that advances ``rows`` sequences must move of the
    recurrent state: each one's state and tails read once and written
    once, in every linear layer."""
    n_linear, _ = layer_kinds(spec)
    return 2.0 * rows * n_linear * (
        STATE_BYTES * state_values(spec)
        + dtype_bytes * conv_tail_values(spec))


def position_bytes(spec: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of one position in all the softmax layers."""
    hd = spec["hidden_size"] // spec["num_attention_heads"]
    return 2 * spec["num_key_value_heads"] * hd * dtype_bytes \
        * layer_kinds(spec)[1]


def cache_bytes(spec: dict, contexts: list, dtype_bytes: int = 2) -> float:
    """What one decode step reads of the softmax layers' slabs: contexts
    are the lengths BEFORE the step, the step's own position is seen
    too."""
    return position_bytes(spec, dtype_bytes) * sum(n + 1 for n in contexts)


def attention_flops(spec: dict, contexts: list) -> float:
    """QK^T and PV of one decode step's softmax layers, and the three
    products with the state of its linear layers."""
    n_linear, n_softmax = layer_kinds(spec)
    softmax = 2 * 2.0 * spec["hidden_size"] * n_softmax * sum(
        n + 1 for n in contexts)
    return softmax + 3 * 2.0 * state_values(spec) * n_linear * len(contexts)


def _weight_bytes(c: dict, spec: dict, dtype_bytes: int) -> float:
    return dtype_bytes * (c["layers"] + c["head"] + spec["hidden_size"])


def decode_step(spec: dict, contexts: list, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step)."""
    c = counts(spec)
    rows = len(contexts)
    per_token = (c["n_linear"] * c["linear_matmul"]
                 + c["n_softmax"] * c["softmax_matmul"]
                 + (c["n_linear"] + c["n_softmax"]) * c["mlp"] + c["head"])
    return {
        "flops": 2.0 * per_token * rows + attention_flops(spec, contexts),
        "bytes": _weight_bytes(c, spec, dtype_bytes)
        + state_bytes(spec, rows, dtype_bytes)
        + cache_bytes(spec, contexts, dtype_bytes)
        + dtype_bytes * spec["hidden_size"] * rows,
        "attention_flops": attention_flops(spec, contexts),
        "cache_bytes": cache_bytes(spec, contexts, dtype_bytes),
        "state_bytes": state_bytes(spec, rows, dtype_bytes),
    }


def prefill_chunk(spec: dict, start: float, tokens: float,
                  dtype_bytes: int = 2) -> dict:
    """``tokens`` prompt tokens of one sequence at positions start ..
    start + tokens - 1, logits for one position."""
    c = counts(spec)
    per_token = (c["n_linear"] * c["linear_matmul"]
                 + c["n_softmax"] * c["softmax_matmul"]
                 + (c["n_linear"] + c["n_softmax"]) * c["mlp"])
    pairs = tokens * start + tokens * (tokens + 1) / 2
    attention = (2 * 2.0 * spec["hidden_size"] * c["n_softmax"] * pairs
                 + 3 * 2.0 * state_values(spec) * c["n_linear"] * tokens)
    return {
        "flops": 2.0 * per_token * tokens + 2.0 * c["head"] + attention,
        "bytes": _weight_bytes(c, spec, dtype_bytes)
        + state_bytes(spec, 1, dtype_bytes)
        + position_bytes(spec, dtype_bytes) * (start + 2 * tokens)
        + dtype_bytes * spec["hidden_size"] * tokens,
        "attention_flops": attention,
        "state_bytes": state_bytes(spec, 1, dtype_bytes),
    }
