"""Required operations and bytes of a decoder whose layers are LINEAR
(a gated delta rule with a state a head) and SOFTMAX ones mixed, routed
and held as a share (``configs/solar-open2.json``), from its
configuration file; the rules are ``opsbytes.py``'s — what the
algorithm needs, a multiply-add is 2 operations, the embedding is a
gather, norms, decays, gates, softmax and the router's top-k are not
counted — with what is this family's own:

* a LINEAR layer keeps nothing of a position: of a sequence it keeps
  the state, ``num_heads * head_dim * head_dim`` float32 values, and
  the last ``short_conv_kernel_size - 1`` inputs of the convolution
  over q, k and v.  A decode step reads and writes both ONCE for every
  row it decodes — not for the slots that sit the step out, which a
  program may touch all the same — and multiplies the state three
  times a row: with the key, with the write, with the query;
* a SOFTMAX layer (on ``gqa_layers``) keeps ``2 * num_key_value_heads
  * head_dim`` values a position and a decode step reads every live
  position of a context: what is READ, not what a slab reserves;
* a decode step reads every weight HELD once — the head's slice once,
  of the embedding its rows only — except the routed experts, of which
  it reads those HIT: the share ``experts_hit`` of the experts held,
  which the caller takes from the program's routing counters
  (``moe_decode_*``);
* a token multiplies with the shared expert, the router, and with as
  many held experts as its assignments fell on: ``local_per_token``
  (``num_experts_per_tok`` times the local share), from the counters
  too.  What the absent experts would cost is someone else's;
* a prefill chunk is ONE sequence's: it reads and writes that slot's
  state and tails once in every linear layer, multiplies the state
  three times a token as a step does (the recurrence's products: the
  block form's products inside a block are how a program does it, not
  what the algorithm needs), reads the softmax layers' live positions
  once and writes its own.

The file's ``n_routed_experts`` is the count of experts held; the
router's width is ``deployment.router_width``; of the layers the first
``num_hidden_layers`` are run, those on ``gqa_layers`` softmax layers.
"""

from __future__ import annotations

STATE_BYTES = 4          # the state is float32 whatever the weights are


def layer_kinds(spec: dict) -> tuple:
    """(linear layers, softmax layers) of the layers run."""
    n = spec["num_hidden_layers"]
    n_softmax = sum(i < n for i in spec["gqa_layers"])
    return n - n_softmax, n_softmax


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads, kvh, hd = (spec["hidden_size"], spec["num_attention_heads"],
                         spec["num_key_value_heads"], spec["head_dim"])
    lin = spec["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], hd
    # wq, wo and the gate; wk, wv
    softmax_matmul = 3 * d * heads * hd + 2 * d * kvh * hd
    # wq, wk, wv, wo; the decay's and the gate's low-rank pairs; w_beta
    linear_matmul = (4 * d * width + 2 * (d * rank + rank * width)
                     + d * lin["num_heads"])
    # the taps, dt_bias, the norm a head, a_log
    linear_small = (lin["short_conv_kernel_size"] * 3 * width + width
                    + lin["head_dim"] + lin["num_heads"])
    expert = 3 * d * spec["moe_intermediate_size"]
    shared = spec["n_shared_experts"] * expert
    router = d * spec["deployment"]["router_width"]
    held = spec["n_routed_experts"]
    n_linear, n_softmax = layer_kinds(spec)
    ffn = shared + router + 2 * d                    # and the two norms
    embed = spec["vocab_size"] * d
    outside = (n_linear * (linear_matmul + linear_small + ffn)
               + n_softmax * (softmax_matmul + ffn))
    return {"softmax_matmul": softmax_matmul,
            "linear_matmul": linear_matmul, "expert": expert,
            "shared": shared, "router": router, "held": held,
            "n_linear": n_linear, "n_softmax": n_softmax,
            "embed": embed, "head": embed, "outside_experts": outside,
            "total": outside + (n_linear + n_softmax) * held * expert
            + 2 * embed + d}


def state_values(spec: dict) -> int:
    """float32 values of one slot's state in one linear layer."""
    lin = spec["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def conv_tail_values(spec: dict) -> int:
    """Values of one slot's convolution tails in one linear layer."""
    lin = spec["linear_attn_config"]
    return (lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"] \
        * lin["head_dim"]


def state_bytes(spec: dict, rows: int, dtype_bytes: int = 2) -> float:
    """What a decode step of ``rows`` rows must move of the recurrent
    state: each row's state and tails read once and written once, in
    every linear layer."""
    n_linear, _ = layer_kinds(spec)
    return 2.0 * rows * n_linear * (
        STATE_BYTES * state_values(spec)
        + dtype_bytes * conv_tail_values(spec))


def cache_bytes(spec: dict, contexts: list, dtype_bytes: int = 2) -> float:
    """What one decode step reads of the softmax layers' slabs: contexts
    are the lengths BEFORE the step, the step's own position is seen
    too."""
    _, n_softmax = layer_kinds(spec)
    return (2 * spec["num_key_value_heads"] * spec["head_dim"] * dtype_bytes
            * n_softmax * sum(n + 1 for n in contexts))


def attention_flops(spec: dict, contexts: list) -> float:
    """QK^T and PV of one decode step's softmax layers, and the three
    products with the state of its linear layers."""
    n_linear, n_softmax = layer_kinds(spec)
    softmax = (2 * 2.0 * spec["num_attention_heads"] * spec["head_dim"]
               * n_softmax * sum(n + 1 for n in contexts))
    return softmax + 3 * 2.0 * state_values(spec) * n_linear * len(contexts)


def decode_step(spec: dict, contexts: list, experts_hit: float,
                local_per_token: float, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step);
    ``experts_hit`` in [0, 1], ``local_per_token`` held experts a token."""
    c = counts(spec)
    rows, layers = len(contexts), c["n_linear"] + c["n_softmax"]
    per_token = (c["n_linear"] * c["linear_matmul"]
                 + c["n_softmax"] * c["softmax_matmul"]
                 + layers * (c["shared"] + c["router"]
                             + local_per_token * c["expert"])
                 + c["head"])
    expert_bytes = dtype_bytes * layers * c["held"] * c["expert"] \
        * experts_hit
    weights = dtype_bytes * (c["outside_experts"] + c["head"]
                             + spec["hidden_size"]) + expert_bytes
    return {
        "flops": 2.0 * per_token * rows + attention_flops(spec, contexts),
        "bytes": weights + state_bytes(spec, rows, dtype_bytes)
        + cache_bytes(spec, contexts, dtype_bytes)
        + dtype_bytes * spec["hidden_size"] * rows,
        "attention_flops": attention_flops(spec, contexts),
        "cache_bytes": cache_bytes(spec, contexts, dtype_bytes),
        "state_bytes": state_bytes(spec, rows, dtype_bytes),
        "expert_bytes": expert_bytes,
    }


def prefill_chunk(spec: dict, start: float, tokens: float,
                  experts_hit: float, local_per_token: float,
                  dtype_bytes: int = 2) -> dict:
    """``tokens`` prompt tokens of one sequence at positions start ..
    start + tokens - 1, logits for one position; ``experts_hit`` and
    ``local_per_token`` as ``decode_step`` takes them, of the chunks."""
    c = counts(spec)
    layers = c["n_linear"] + c["n_softmax"]
    per_token = (c["n_linear"] * c["linear_matmul"]
                 + c["n_softmax"] * c["softmax_matmul"]
                 + layers * (c["shared"] + c["router"]
                             + local_per_token * c["expert"]))
    pairs = tokens * start + tokens * (tokens + 1) / 2
    attention = (2 * 2.0 * spec["num_attention_heads"] * spec["head_dim"]
                 * c["n_softmax"] * pairs
                 + 3 * 2.0 * state_values(spec) * c["n_linear"] * tokens)
    expert_bytes = dtype_bytes * layers * c["held"] * c["expert"] \
        * experts_hit
    position = 2 * spec["num_key_value_heads"] * spec["head_dim"] \
        * dtype_bytes * c["n_softmax"]
    return {
        "flops": 2.0 * per_token * tokens + 2.0 * c["head"] + attention,
        "bytes": dtype_bytes * (c["outside_experts"] + c["head"]
                                + spec["hidden_size"]) + expert_bytes
        + state_bytes(spec, 1, dtype_bytes)
        + position * (start + 2 * tokens)
        + dtype_bytes * spec["hidden_size"] * tokens,
        "attention_flops": attention,
        "state_bytes": state_bytes(spec, 1, dtype_bytes),
        "expert_bytes": expert_bytes,
    }
