"""Required operations and bytes of a decoder whose layers are WINDOW
and FULL ones mixed, routed and held as a share
(``configs/command-a-plus.json``), from its configuration file; the
rules are ``opsbytes.py``'s — what the algorithm needs, a multiply-add
is 2 operations, the embedding is a gather, norms, rotary embedding,
softmax and the router's top-k are not counted — with what is this
family's own:

* a position's cache is ``2 * num_key_value_heads * head_dim`` values a
  layer; a FULL layer reads every live position of a context, a WINDOW
  layer the newest ``sliding_window`` of them at most — what is READ,
  not what a ring or a slab reserves;
* a head is ``head_dim`` wide, which the file states (heads * head_dim
  is not the hidden size);
* a decode step reads every weight HELD once — the tied embedding once,
  as the head — except the routed experts, of which it reads those HIT:
  the share ``experts_hit`` of the experts held, which the caller takes
  from the program's routing counters (``moe_decode_*``);
* a token multiplies with the shared experts, the router, and with as
  many held experts as its assignments fell on: ``local_per_token``
  (``num_experts_per_tok`` times the local share), from the counters
  too.  What the absent experts would cost is someone else's.

The file's ``num_experts`` is the count of experts held; the router's
width is ``deployment.router_width``; of ``layer_types`` the first
``num_hidden_layers`` are run.
"""

from __future__ import annotations


def layer_kinds(spec: dict) -> tuple:
    """(window layers, full layers) of the layers run."""
    kinds = spec["layer_types"][:spec["num_hidden_layers"]]
    n_window = sum(kind == "sliding_attention" for kind in kinds)
    return n_window, len(kinds) - n_window


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads, kvh = (spec["hidden_size"], spec["num_attention_heads"],
                     spec["num_key_value_heads"])
    hd = spec["head_dim"]
    attn_matmul = 2 * d * heads * hd + 2 * d * kvh * hd
    expert = 3 * d * spec["intermediate_size"]
    shared = spec["num_shared_experts"] * expert
    router = d * spec["deployment"]["router_width"]
    held, layers = spec["num_experts"], spec["num_hidden_layers"]
    embed = spec["vocab_size"] * d
    head = 0 if spec.get("tie_word_embeddings") else embed
    outside = attn_matmul + d + shared + router     # d: the one norm
    return {"attention_matmul": attn_matmul, "expert": expert,
            "shared": shared, "router": router, "held": held,
            "layers": layers, "embed": embed, "head": head,
            "layer": outside + held * expert,
            "total": layers * (outside + held * expert) + embed + head + d}


def pairs_seen(spec: dict, contexts: list) -> int:
    """(query, cached position) pairs of one decode step, summed over
    the layers: contexts are the lengths BEFORE the step, the step's own
    position is seen too; a window layer sees ``sliding_window`` at
    most."""
    n_window, n_full = layer_kinds(spec)
    window = spec["sliding_window"]
    return sum(n_full * (n + 1) + n_window * min(n + 1, window)
               for n in contexts)


def cache_bytes(spec: dict, contexts: list, dtype_bytes: int = 2) -> float:
    """What one decode step reads of the cache."""
    return (2 * spec["num_key_value_heads"] * spec["head_dim"] * dtype_bytes
            * pairs_seen(spec, contexts))


def attention_flops(spec: dict, contexts: list) -> float:
    """QK^T and PV of one decode step."""
    return (2 * 2.0 * spec["num_attention_heads"] * spec["head_dim"]
            * pairs_seen(spec, contexts))


def decode_step(spec: dict, contexts: list, experts_hit: float,
                local_per_token: float, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step);
    ``experts_hit`` in [0, 1], ``local_per_token`` held experts a token."""
    c = counts(spec)
    rows = len(contexts)
    per_token = (c["layers"] * (c["attention_matmul"] + c["shared"]
                                + c["router"]
                                + local_per_token * c["expert"])
                 + spec["hidden_size"] * spec["vocab_size"])
    weights = c["total"] - c["layers"] * c["held"] * c["expert"] * (
        1.0 - experts_hit)
    if c["head"]:
        weights -= c["embed"]      # untied: the embedding's rows only
    return {
        "flops": 2.0 * per_token * rows + attention_flops(spec, contexts),
        "bytes": dtype_bytes * weights + cache_bytes(spec, contexts,
                                                     dtype_bytes)
        + dtype_bytes * spec["hidden_size"] * rows,
        "attention_flops": attention_flops(spec, contexts),
        "cache_bytes": cache_bytes(spec, contexts, dtype_bytes),
        "expert_bytes": dtype_bytes * c["layers"] * c["held"] * c["expert"]
        * experts_hit,
    }
