"""Required operations and bytes of a prefill chunk of a LATENT, ROUTED
decoder whose tokens keep SEVERAL RESIDUAL STREAMS (manifold-constrained
hyper-connections: ``configs/xing4.0-29b-a4b.json``), from its
configuration file.  The rules are ``opsbytes.py``'s — what the
algorithm needs, a multiply-add is 2 operations, the embedding is a
gather, norms, rotary embedding, softmax, sigmoids, the Sinkhorn passes
(96 values a row) and the router's top-k are not counted; the weights'
counts are ``opsbytes_latent.counts``'s — with what is this family's
own:

* a token's residual state is ``hc_mult`` streams of ``hidden_size``
  values in the streams' dtype, and every sub-layer (two a layer) makes
  THREE passes over it: it is read for the norm and the maps' product,
  read again for the mix the sub-layer takes in, and the mixed streams
  are written — ``stream_bytes``.  Nothing else of the chunk's rows is
  as large;
* a sub-layer's maps are a product of the ``hc_mult * hidden_size``
  normed values with ``phi``, ``2 hc_mult + hc_mult^2`` wide; reading
  the streams is ``hc_mult`` multiply-adds a value of the output, mixing
  them and adding the sub-layer's output ``hc_mult^2 + hc_mult`` —
  ``stream_flops``;
* a chunk is ONE sequence's: it reads every weight held once — of the
  embedding its rows only, of the routed experts those HIT (the share
  ``experts_hit``, which the caller takes from the program's routing
  counters less the decode steps' own), the maps' leaves among them —,
  the slot's valid latent positions once a layer, and writes its own;
  logits for one position, the whole untied head read for it;
* attention over the latent cache is counted in whichever of its two
  forms is cheaper for the chunk: ABSORBED (``opsbytes_latent``: every
  head against the one latent, ``2 kv_lora_rank + qk_rope_head_dim`` a
  pair) or PER HEAD (keys and values made of every cached position
  first, ``kv_lora_rank x (nope + v)`` a head, then ``nope + rope + v``
  a pair); the chunk's own tokens' products with ``W_kvb`` are among the
  weights' either way.
"""

from __future__ import annotations

from chipbench import opsbytes_latent

SUB_LAYERS = 2           # attention and the feed-forward, each with maps
STREAM_PASSES = 3        # norm + maps; the read; the mixed streams' write


def map_width(spec: dict) -> int:
    n = spec["hc_mult"]
    return 2 * n + n * n


def maps_params(spec: dict) -> int:
    """One sub-layer's ``phi``, ``b`` and three scalars."""
    width = map_width(spec)
    return spec["hc_mult"] * spec["hidden_size"] * width + width + 3


def stream_bytes(spec: dict, rows: float, dtype_bytes: int = 2) -> float:
    """What ``rows`` tokens' streams cost to move through all layers."""
    return (STREAM_PASSES * SUB_LAYERS * spec["num_hidden_layers"] * rows
            * spec["hc_mult"] * spec["hidden_size"] * dtype_bytes)


def stream_flops(spec: dict, rows: float) -> float:
    """The maps' products, the reads and the mixes of ``rows`` tokens,
    all layers."""
    n, d = spec["hc_mult"], spec["hidden_size"]
    a_sub_layer = 2.0 * n * d * map_width(spec) + 2.0 * n * d \
        + 2.0 * (n * n + n) * d
    return a_sub_layer * SUB_LAYERS * spec["num_hidden_layers"] * rows


def attention_flops(spec: dict, start: float, tokens: float) -> float:
    """A chunk's scores and values over the slot's latent cache, all
    layers, in the cheaper of the two forms."""
    heads, rkv = spec["num_attention_heads"], spec["kv_lora_rank"]
    nope, rope, v = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                     spec["v_head_dim"])
    pairs = tokens * start + tokens * (tokens + 1) / 2
    absorbed = opsbytes_latent.absorbed_attention_flops(spec, pairs)
    per_head = spec["num_hidden_layers"] * 2.0 * heads * (
        (nope + rope + v) * pairs + rkv * (nope + v) * start)
    return min(absorbed, per_head)


def prefill_chunk(spec: dict, start: float, tokens: float,
                  experts_hit: float, dtype_bytes: int = 2) -> dict:
    """``tokens`` prompt tokens of one sequence at positions start ..
    start + tokens - 1, logits for one position; ``experts_hit`` in
    [0, 1]: the share of the held routed experts its rows fell on."""
    c = opsbytes_latent.counts(spec)
    layers = c["n_dense"] + c["n_moe"]
    maps = SUB_LAYERS * layers * maps_params(spec)
    per_token = (layers * c["attention_matmul"]
                 + c["n_dense"] * c["dense_mlp"]
                 + c["n_moe"] * (c["shared"] + c["router"]
                                 + spec["num_experts_per_tok"] * c["expert"]))
    weights = (c["total"] - c["embed"] + maps
               - c["n_moe"] * c["held"] * c["expert"] * (1.0 - experts_hit))
    attention = attention_flops(spec, start, tokens)
    streams = {"bytes": stream_bytes(spec, tokens, dtype_bytes),
               "flops": stream_flops(spec, tokens)}
    return {
        "flops": 2.0 * per_token * tokens + 2.0 * c["head"] + attention
        + streams["flops"],
        "bytes": dtype_bytes * weights
        + opsbytes_latent.cache_bytes_per_position(spec, dtype_bytes)
        * (start + 2 * tokens)
        + dtype_bytes * spec["hidden_size"] * tokens + streams["bytes"],
        "attention_flops": attention,
        "stream_bytes": streams["bytes"], "stream_flops": streams["flops"],
    }


def least_seconds(need: dict, peaks: dict) -> tuple:
    """(the least time ``need`` could take on a chip of ``peaks``, the
    part of it that is the residual streams' own): the larger of bytes
    over bandwidth and operations over the bf16 peak, and the streams'
    bytes or operations over the same."""
    by = {"bytes": peaks["hbm_bytes_per_s"],
          "flops": peaks["bf16_flops_per_s"]}
    bound = max(by, key=lambda what: need[what] / by[what])
    return need[bound] / by[bound], need["stream_" + bound] / by[bound]
