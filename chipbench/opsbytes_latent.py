"""Required operations and bytes of a LATENT, ROUTED decoder held as a
share (``configs/ax-k1.json``), from its configuration file; the rules
are ``opsbytes.py``'s — what the algorithm needs, a multiply-add is 2
operations, the embedding is a gather, norms, rotary embedding, softmax
and the router's top-k are not counted — with what is this family's own:

* a position's cache is its latent and its one rotary key,
  ``kv_lora_rank + qk_rope_head_dim`` values a layer, whatever the
  number of heads;
* decode attention is counted in the ABSORBED form, the one a latent
  cache is read in: a (query, cached position) pair costs every head a
  product of ``kv_lora_rank + qk_rope_head_dim`` for its score and one
  of ``kv_lora_rank`` for its value; absorbing ``W_kvb`` into the query
  and onto the output costs a token what one product with ``W_kvb``
  would;
* a decode step reads every weight HELD once, except the embedding (its
  rows only) and the routed experts, of which it reads those HIT: the
  share ``experts_hit`` of the experts held, which the caller takes
  from the program's routing counters;
* a token multiplies with the shared expert, the router, and with as
  many held experts as its assignments fell on: ``local_per_token``
  (``num_experts_per_tok`` times the local share), from the counters
  too.  What the absent experts would cost is someone else's.

The file's ``n_routed_experts`` is the count of experts held; the
router's width is ``deployment.router_width``.
"""

from __future__ import annotations


def counts(spec: dict) -> dict:
    """Parameters held, by part."""
    d, heads = spec["hidden_size"], spec["num_attention_heads"]
    rq, rkv = spec["q_lora_rank"], spec["kv_lora_rank"]
    nope, rope, v = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                     spec["v_head_dim"])
    attn_matmul = (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
                   + rkv * heads * (nope + v) + heads * v * d)
    norms = 2 * d + rq + rkv
    expert = 3 * d * spec["moe_intermediate_size"]
    shared = spec["n_shared_experts"] * expert
    router = d * spec["deployment"]["router_width"]
    dense_mlp = 3 * d * spec["intermediate_size"]
    n_dense = spec["first_k_dense_replace"]
    n_moe = spec["num_hidden_layers"] - n_dense
    held = spec["n_routed_experts"]
    vocab = spec["vocab_size"]
    head = 0 if spec.get("tie_word_embeddings") else d * vocab
    return {
        "attention": attn_matmul + norms, "attention_matmul": attn_matmul,
        "expert": expert, "shared": shared, "router": router,
        "moe_layer": attn_matmul + norms + shared + router + held * expert,
        "dense_layer": attn_matmul + norms + dense_mlp,
        "dense_mlp": dense_mlp, "embed": vocab * d, "head": head,
        "n_dense": n_dense, "n_moe": n_moe, "held": held,
        "total": (n_dense * (attn_matmul + norms + dense_mlp)
                  + n_moe * (attn_matmul + norms + shared + router
                             + held * expert)
                  + vocab * d + head + d),
    }


def cache_bytes_per_position(spec: dict, dtype_bytes: int = 2) -> int:
    return (spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) \
        * spec["num_hidden_layers"] * dtype_bytes


def absorbed_attention_flops(spec: dict, pairs: float) -> float:
    """Score and value products over ``pairs`` (query, cached position)
    pairs, all layers, every head against the one latent."""
    rkv, rope = spec["kv_lora_rank"], spec["qk_rope_head_dim"]
    return 2.0 * spec["num_attention_heads"] * (rkv + rope + rkv) * pairs \
        * spec["num_hidden_layers"]


def decode_step(spec: dict, contexts: list, experts_hit: float,
                local_per_token: float, dtype_bytes: int = 2) -> dict:
    """One token for each active context (lengths BEFORE the step);
    ``experts_hit`` in [0, 1], ``local_per_token`` held experts a token."""
    c = counts(spec)
    rows = len(contexts)
    pairs = sum(n + 1 for n in contexts)
    layers = c["n_dense"] + c["n_moe"]
    per_token = (layers * c["attention_matmul"]
                 + c["n_dense"] * c["dense_mlp"]
                 + c["n_moe"] * (c["shared"] + c["router"]
                                 + local_per_token * c["expert"])
                 + spec["hidden_size"] * spec["vocab_size"])
    weights = (c["total"] - c["embed"]
               - c["n_moe"] * c["held"] * c["expert"] * (1.0 - experts_hit))
    return {
        "flops": 2.0 * per_token * rows
        + absorbed_attention_flops(spec, pairs),
        "bytes": dtype_bytes * weights
        + cache_bytes_per_position(spec, dtype_bytes) * pairs
        + dtype_bytes * spec["hidden_size"] * rows,
        "attention_flops": absorbed_attention_flops(spec, pairs),
        "expert_bytes": dtype_bytes * c["n_moe"] * c["held"] * c["expert"]
        * experts_hit,
    }
