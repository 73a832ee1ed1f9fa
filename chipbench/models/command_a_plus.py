"""The Command A+ configuration file -> the program's ``LlamaConfig``
(window and full layers in one model, a stated head width, ONE LayerNorm
over a parallel block, a sigmoid router over all experts of which a
share is held, shared experts averaged, the embedding tied), and the
program's parameter tree -> the layout
``reference/command_a_plus_decoder.py`` reads.  Imported only inside
workers: it imports jax.

How the share is written into the file: ``num_experts`` is the count of
experts this chip HOLDS (published 128, listed under ``reduced``),
``deployment.router_width`` the width the router keeps (the published
128), ``deployment.experts_held`` the first and the last expert held;
``vocab_size`` is the slice of the vocabulary held; ``layer_types`` is
the published list, of which the first ``num_hidden_layers`` are run.
"""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "configs", "command-a-plus.json")
_KINDS = {"sliding_attention": True, "full_attention": False}


def _pattern(layer_types: list) -> tuple:
    """The shortest period of ``layer_types`` -> for each of its places
    whether the layer there is a window layer."""
    kinds = [_KINDS[kind] for kind in layer_types]
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple(kinds[:n])
    raise AssertionError("unreachable: a list is its own period")


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    share = spec["deployment"]
    first, last = share["experts_held"]
    if spec.get("attention_bias") or spec["use_qk_norm"] \
            or not spec["use_parallel_block"] or spec["rotary_pct"] != 1 \
            or spec["first_k_dense_replace"] or spec["hidden_act"] != "silu" \
            or not spec["use_gated_activation"] or spec["logit_scale"] != 1 \
            or spec["rope_parameters"]["rope_type"] != "default" \
            or spec["expert_selection_fn"] != "sigmoid" \
            or spec["shared_expert_combination_strategy"] != "average" \
            or spec["position_embedding_type"] != "rope_gptj":
        raise ValueError(
            "biases, QK-norm, a sequential block, partial or scaled "
            "rotary embeddings, leading dense layers, ungated or other "
            "activations, a logit scale, a softmax router and shared "
            "experts combined otherwise than by their average are not "
            "what chipbench/models/command_a_plus.py maps")
    if last - first + 1 != spec["num_experts"] \
            or last >= share["router_width"]:
        raise ValueError("experts_held does not name num_experts experts "
                         "of the router's width")
    pattern = _pattern(spec["layer_types"][:spec["num_hidden_layers"]])
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_width=spec["head_dim"], mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["layer_norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        num_experts=spec["num_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        norm_topk_prob=bool(spec["norm_topk_prob"]),
        router_scoring=spec["expert_selection_fn"],
        router_width=share["router_width"], first_expert=first,
        n_shared_experts=spec["num_shared_experts"],
        shared_experts_average=True,
        window=spec["sliding_window"] if any(pattern) else 0,
        window_pattern=pattern if any(pattern) else (),
        full_rope=False, norm="layer", parallel_block=True)


def reference_layers(params: dict, layer_types: list | None = None,
                     head_dim: int | None = None):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/command_a_plus_decoder.py`` names them; ``layer(i)``
    takes one layer out of the stack when asked and gives it its KIND
    (``windowed``, 1.0 or 0.0), from ``layer_types``; that list and a
    head's width ``head_dim``, which no shape of the tree gives, are by
    default those of ``configs/command-a-plus.json``.

    ONE thing is re-laid: the program rotates a head in the half-split
    order (pairs (j, j + hd/2), ``ops/rope.py``), the published weights
    (``rope_gptj``) and the reference in pairs (2j, 2j + 1).  The
    program's random weights are read as already permuted, so the
    reference gets every head's columns of ``wq`` and ``wk`` put back in
    the published order: the inverse of
    ``rope.half_split_from_interleaved``, which a loader of published
    weights would apply.  Applied to queries and keys alike it changes
    no score — of a full layer, which rotates nothing, neither."""
    import jax.numpy as jnp

    from ant_ray_tpu.ops.rope import half_split_from_interleaved

    if layer_types is None or head_dim is None:
        with open(_FILE) as f:
            published = json.load(f)
        layer_types = layer_types or published["layer_types"]
        head_dim = head_dim or published["head_dim"]
    stack = params["layers"]
    order = jnp.argsort(half_split_from_interleaved(head_dim))

    def published_columns(w):
        by_head = w.reshape(w.shape[0], -1, head_dim)
        return by_head[..., order].reshape(w.shape)

    def layer(i: int) -> dict:
        out = {"attn_norm" if own == "ln_attn" else own: leaf[i]
               for own, leaf in stack.items()}
        out["wq"] = published_columns(out["wq"])
        out["wk"] = published_columns(out["wk"])
        out["windowed"] = jnp.float32(_KINDS[layer_types[i]])
        return out

    n_layers = stack["ln_attn"].shape[0]
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["embed"].T
