"""Required operations and bytes of a decoder whose layers are
STATE-SPACE ones (Mamba-2's selective recurrence, a state a head) and
SOFTMAX ones mixed, routed and held as a share
(``configs/granite-4.0-h-small.json``), from its configuration file;
the rules are ``opsbytes.py``'s — what the algorithm needs, a
multiply-add is 2 operations, the embedding is a gather, norms, decays,
gates, softmax and the router's top-k are not counted — with what is
this family's own:

* a STATE-SPACE layer (``layer_types`` ``mamba``) keeps nothing of a
  position: of a sequence it keeps the state, ``mamba_n_heads *
  mamba_d_head * mamba_d_state`` float32 values, and the last
  ``mamba_d_conv - 1`` inputs of the convolution over the heads' inputs
  and the two directions.  A decode step reads and writes both ONCE for
  every row it decodes — not for the slots that sit the step out, which
  a program may touch all the same — and multiplies with the state
  twice a row: the write (``dt x B^T``) and the read (``S C``);
* a SOFTMAX layer (``attention``) keeps ``2 * num_key_value_heads *
  head_dim`` values a position and a decode step reads every live
  position of a context: what is READ, not what a slab reserves;
* a decode step reads every weight HELD once — the tied embedding's
  slice once as the head, of it as the embedding its rows only — except
  the routed experts, of which it reads those HIT: the share
  ``experts_hit`` of the experts held, which the caller takes from the
  program's routing counters (``moe_decode_*``);
* a token multiplies with the shared expert, the router, and with as
  many held experts as its assignments fell on: ``local_per_token``
  (``num_experts_per_tok`` times the local share), from the counters
  too.  What the absent experts would cost is someone else's;
* a prefill chunk is ONE sequence's: it reads and writes that slot's
  state and tail once in every state-space layer, multiplies with the
  state twice a token as a step does (the recurrence's products: the
  block form's products inside a block are how a program does it, not
  what the algorithm needs), reads the softmax layers' live positions
  once and writes its own.

The file's ``num_local_experts`` is the count of experts held; the
router's width is ``deployment.router_width``; of ``layer_types`` the
first ``num_hidden_layers`` are run; a softmax head is ``hidden_size /
num_attention_heads`` wide (the config has no key of its own).
"""

from __future__ import annotations

STATE_BYTES = 4          # the state is float32 whatever the weights are


def layer_kinds(spec: dict) -> tuple:
    """(state-space layers, softmax layers) of the layers run."""
    run = spec["layer_types"][:spec["num_hidden_layers"]]
    return run.count("mamba"), run.count("attention")


def head_dim(spec: dict) -> int:
    return spec["hidden_size"] // spec["num_attention_heads"]


def conv_channels(spec: dict) -> int:
    """The heads' inputs and the write and the read direction."""
    return (spec["mamba_n_heads"] * spec["mamba_d_head"]
            + 2 * spec["mamba_n_groups"] * spec["mamba_d_state"])


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads, kvh, hd = (spec["hidden_size"], spec["num_attention_heads"],
                         spec["num_key_value_heads"], head_dim(spec))
    ssm_heads = spec["mamba_n_heads"]
    inner, channels = ssm_heads * spec["mamba_d_head"], conv_channels(spec)
    softmax_matmul = 2 * d * heads * hd + 2 * d * kvh * hd
    # in_proj (the gate, the convolution's channels, a step a head) and
    # out_proj
    ssm_matmul = d * (inner + channels + ssm_heads) + inner * d
    # the taps and their bias, dt_bias, A_log and D, the gated norm
    ssm_small = (spec["mamba_d_conv"] + 1) * channels + 3 * ssm_heads + inner
    expert = 3 * d * spec["intermediate_size"]
    shared = 3 * d * spec["shared_intermediate_size"]
    router = d * spec["deployment"]["router_width"]
    held = spec["num_local_experts"]
    n_ssm, n_softmax = layer_kinds(spec)
    ffn = shared + router + 2 * d                    # and the two norms
    embed = spec["vocab_size"] * d
    outside = (n_ssm * (ssm_matmul + ssm_small + ffn)
               + n_softmax * (softmax_matmul + ffn))
    return {"softmax_matmul": softmax_matmul, "ssm_matmul": ssm_matmul,
            "expert": expert, "shared": shared, "router": router,
            "held": held, "n_ssm": n_ssm, "n_softmax": n_softmax,
            "embed": embed, "head": embed, "outside_experts": outside,
            # tied: the embedding is held once
            "total": outside + (n_ssm + n_softmax) * held * expert
            + embed + d}


def state_values(spec: dict) -> int:
    """float32 values of one slot's state in one state-space layer."""
    return (spec["mamba_n_heads"] * spec["mamba_d_head"]
            * spec["mamba_d_state"])


def conv_tail_values(spec: dict) -> int:
    """Values of one slot's convolution tail in one state-space layer."""
    return (spec["mamba_d_conv"] - 1) * conv_channels(spec)


def state_bytes(spec: dict, rows: int, dtype_bytes: int = 2) -> float:
    """What a decode step of ``rows`` rows must move of the recurrent
    state: each row's state and tail read once and written once, in
    every state-space layer."""
    n_ssm, _ = layer_kinds(spec)
    return 2.0 * rows * n_ssm * (
        STATE_BYTES * state_values(spec)
        + dtype_bytes * conv_tail_values(spec))


def cache_bytes(spec: dict, contexts: list, dtype_bytes: int = 2) -> float:
    """What one decode step reads of the softmax layers' slabs: contexts
    are the lengths BEFORE the step, the step's own position is seen
    too."""
    _, n_softmax = layer_kinds(spec)
    return (2 * spec["num_key_value_heads"] * head_dim(spec) * dtype_bytes
            * n_softmax * sum(n + 1 for n in contexts))


def attention_flops(spec: dict, contexts: list) -> float:
    """QK^T and PV of one decode step's softmax layers, and the two
    products with the state of its state-space layers."""
    n_ssm, n_softmax = layer_kinds(spec)
    softmax = (2 * 2.0 * spec["num_attention_heads"] * head_dim(spec)
               * n_softmax * sum(n + 1 for n in contexts))
    return softmax + 2 * 2.0 * state_values(spec) * n_ssm * len(contexts)


def _per_token(c: dict, local_per_token: float) -> float:
    """Parameters a token multiplies with, the head apart."""
    layers = c["n_ssm"] + c["n_softmax"]
    return (c["n_ssm"] * c["ssm_matmul"]
            + c["n_softmax"] * c["softmax_matmul"]
            + layers * (c["shared"] + c["router"]
                        + local_per_token * c["expert"]))


def _weight_bytes(spec: dict, c: dict, experts_hit: float,
                  dtype_bytes: int) -> tuple:
    """(every weight a call reads, of them the routed experts')."""
    layers = c["n_ssm"] + c["n_softmax"]
    expert_bytes = dtype_bytes * layers * c["held"] * c["expert"] \
        * experts_hit
    return dtype_bytes * (c["outside_experts"] + c["head"]
                          + spec["hidden_size"]) + expert_bytes, expert_bytes


def decode_step(spec: dict, contexts: list, experts_hit: float,
                local_per_token: float, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step);
    ``experts_hit`` in [0, 1], ``local_per_token`` held experts a token."""
    c = counts(spec)
    rows = len(contexts)
    weights, expert_bytes = _weight_bytes(spec, c, experts_hit, dtype_bytes)
    return {
        "flops": 2.0 * (_per_token(c, local_per_token) + c["head"]) * rows
        + attention_flops(spec, contexts),
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
    pairs = tokens * start + tokens * (tokens + 1) / 2
    attention = (2 * 2.0 * spec["num_attention_heads"] * head_dim(spec)
                 * c["n_softmax"] * pairs
                 + 2 * 2.0 * state_values(spec) * c["n_ssm"] * tokens)
    weights, expert_bytes = _weight_bytes(spec, c, experts_hit, dtype_bytes)
    position = 2 * spec["num_key_value_heads"] * head_dim(spec) \
        * dtype_bytes * c["n_softmax"]
    return {
        "flops": 2.0 * _per_token(c, local_per_token) * tokens
        + 2.0 * c["head"] + attention,
        "bytes": weights + state_bytes(spec, 1, dtype_bytes)
        + position * (start + 2 * tokens)
        + dtype_bytes * spec["hidden_size"] * tokens,
        "attention_flops": attention,
        "state_bytes": state_bytes(spec, 1, dtype_bytes),
        "expert_bytes": expert_bytes,
    }
