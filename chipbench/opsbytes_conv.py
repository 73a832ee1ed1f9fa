"""Required operations and bytes of a decoder whose layers are GATED
SHORT-CONVOLUTION ones (a convolution tail a slot and no state matrix)
and SOFTMAX ones mixed, with leading dense layers and routed experts
all of which are held (``configs/lfm2-8b-a1b.json``), from its
configuration file; the rules are ``opsbytes.py``'s and
``opsbytes_ssm.py``'s — what the algorithm needs, a multiply-add is 2
operations, the embedding is a gather, norms, gates, softmax and the
router's top-k are not counted — with what is this family's own:

* a CONV layer (``layer_types`` ``conv``) keeps nothing of a position:
  of a sequence it keeps the last ``conv_L_cache - 1`` gated inputs of
  its convolution, ``hidden_size`` values each.  A decode step reads
  and writes that tail ONCE for every row it decodes — not for the
  slots that sit the step out, which a program may touch all the same
  — and multiplies a channel with its ``conv_L_cache`` taps;
* a SOFTMAX layer (``full_attention``) keeps ``2 *
  num_key_value_heads * head_dim`` values a position and a decode step
  reads every LIVE position of a context: what is read, not what a slab
  reserves;
* a decode step reads every weight outside the routed experts once —
  the tied embedding once as the head, of it as the embedding its rows
  only — and of the routed experts those HIT: the share
  ``experts_hit`` of the experts of the routed layers, which the caller
  takes from the program's routing counters (``moe_decode_*``), never
  all of them by default;
* a token multiplies with the router and with ``num_experts_per_tok``
  experts in every routed layer (all experts are held: none of a
  token's picks is someone else's), with the dense SwiGLU in the
  leading ``num_dense_layers``;
* a prefill chunk is ONE sequence's: it reads and writes that slot's
  tail once in every conv layer, reads the softmax layers' live
  positions once and writes its own, and multiplies for its REAL
  tokens only.

Of ``layer_types`` the first ``num_hidden_layers`` are run; a softmax
head is ``hidden_size / num_attention_heads`` wide (the config has no
key of its own); the head is the embedding (tied).
"""

from __future__ import annotations

from chipbench.opsbytes_ssm import head_dim


def layer_kinds(spec: dict) -> tuple:
    """(conv layers, softmax layers) of the layers run."""
    run = spec["layer_types"][:spec["num_hidden_layers"]]
    return run.count("conv"), run.count("full_attention")


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads, kvh, hd = (spec["hidden_size"], spec["num_attention_heads"],
                         spec["num_key_value_heads"], head_dim(spec))
    taps = spec["conv_L_cache"]
    # wq, wo; wk, wv — and the q and k norms' one weight a head each
    softmax_matmul = 2 * d * heads * hd + 2 * d * kvh * hd
    softmax_small = 2 * hd
    # in_proj (B, C and u) and out_proj; the taps
    conv_matmul = d * 3 * d + d * d
    conv_small = taps * d
    expert = 3 * d * spec["moe_intermediate_size"]
    dense = 3 * d * spec["intermediate_size"]
    router = d * spec["num_experts"]
    n_conv, n_softmax = layer_kinds(spec)
    n_dense = spec["num_dense_layers"]
    n_routed = n_conv + n_softmax - n_dense
    mixers = (n_conv * (conv_matmul + conv_small)
              + n_softmax * (softmax_matmul + softmax_small))
    # two norms a layer; the router and its correction bias
    outside = (mixers + (n_conv + n_softmax) * 2 * d + n_dense * dense
               + n_routed * (router + spec["num_experts"]))
    embed = spec["vocab_size"] * d
    return {"softmax_matmul": softmax_matmul, "conv_matmul": conv_matmul,
            "conv_taps": conv_small, "expert": expert, "dense": dense,
            "router": router, "experts": spec["num_experts"],
            "n_conv": n_conv, "n_softmax": n_softmax, "n_dense": n_dense,
            "n_routed": n_routed, "embed": embed, "head": embed,
            "outside_experts": outside,
            # tied: the embedding is held once; the final norm
            "total": outside + n_routed * spec["num_experts"] * expert
            + embed + d}


def tail_values(spec: dict) -> int:
    """Values of one slot's convolution tail in one conv layer."""
    return (spec["conv_L_cache"] - 1) * spec["hidden_size"]


def tail_bytes(spec: dict, rows: int, dtype_bytes: int = 2) -> float:
    """What a decode step of ``rows`` rows must move of the tails: each
    row's read once and written once, in every conv layer."""
    n_conv, _ = layer_kinds(spec)
    return 2.0 * rows * n_conv * dtype_bytes * tail_values(spec)


def position_bytes(spec: dict, dtype_bytes: int = 2) -> int:
    """What the softmax layers keep of ONE position."""
    _, n_softmax = layer_kinds(spec)
    return (2 * spec["num_key_value_heads"] * head_dim(spec) * dtype_bytes
            * n_softmax)


def cache_bytes(spec: dict, contexts: list, dtype_bytes: int = 2) -> float:
    """What one decode step reads of the softmax layers' slabs: contexts
    are the lengths BEFORE the step, the step's own position is seen
    too."""
    return position_bytes(spec, dtype_bytes) * sum(n + 1 for n in contexts)


def attention_flops(spec: dict, contexts: list) -> float:
    """QK^T and PV of one decode step's softmax layers."""
    _, n_softmax = layer_kinds(spec)
    return (2 * 2.0 * spec["num_attention_heads"] * head_dim(spec)
            * n_softmax * sum(n + 1 for n in contexts))


def _per_token(spec: dict, c: dict) -> float:
    """Parameters a token multiplies with, the head apart: the mixers'
    matrices, a tap a multiply-add a channel, the dense SwiGLUs, the
    routers and the experts picked."""
    return (c["n_conv"] * (c["conv_matmul"] + c["conv_taps"])
            + c["n_softmax"] * c["softmax_matmul"]
            + c["n_dense"] * c["dense"]
            + c["n_routed"] * (c["router"]
                               + spec["num_experts_per_tok"] * c["expert"]))


def _weight_bytes(spec: dict, c: dict, experts_hit: float,
                  dtype_bytes: int) -> tuple:
    """(every weight a call reads, of them the routed experts')."""
    expert_bytes = (dtype_bytes * c["n_routed"] * c["experts"] * c["expert"]
                    * experts_hit)
    return dtype_bytes * (c["outside_experts"] + c["head"]
                          + spec["hidden_size"]) + expert_bytes, expert_bytes


def decode_step(spec: dict, contexts: list, experts_hit: float,
                dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step);
    ``experts_hit`` in [0, 1]: of the routed layers' experts, the share
    given a row."""
    c = counts(spec)
    rows = len(contexts)
    weights, expert_bytes = _weight_bytes(spec, c, experts_hit, dtype_bytes)
    return {
        "flops": 2.0 * (_per_token(spec, c) + c["head"]) * rows
        + attention_flops(spec, contexts),
        "bytes": weights + tail_bytes(spec, rows, dtype_bytes)
        + cache_bytes(spec, contexts, dtype_bytes)
        + dtype_bytes * spec["hidden_size"] * rows,
        "attention_flops": attention_flops(spec, contexts),
        "cache_bytes": cache_bytes(spec, contexts, dtype_bytes),
        "tail_bytes": tail_bytes(spec, rows, dtype_bytes),
        "expert_bytes": expert_bytes,
    }


def prefill_chunk(spec: dict, start: float, tokens: float,
                  experts_hit: float, dtype_bytes: int = 2) -> dict:
    """``tokens`` REAL prompt tokens of one sequence at positions start
    .. start + tokens - 1, logits for one position; ``experts_hit`` as
    ``decode_step`` takes it, of the chunks."""
    c = counts(spec)
    pairs = tokens * start + tokens * (tokens + 1) / 2
    attention = (2 * 2.0 * spec["num_attention_heads"] * head_dim(spec)
                 * c["n_softmax"] * pairs)
    weights, expert_bytes = _weight_bytes(spec, c, experts_hit, dtype_bytes)
    return {
        "flops": 2.0 * _per_token(spec, c) * tokens + 2.0 * c["head"]
        + attention,
        "bytes": weights + tail_bytes(spec, 1, dtype_bytes)
        + position_bytes(spec, dtype_bytes) * (start + 2 * tokens)
        + dtype_bytes * spec["hidden_size"] * tokens,
        "attention_flops": attention,
        "tail_bytes": tail_bytes(spec, 1, dtype_bytes),
        "expert_bytes": expert_bytes,
    }
