"""The Solar Open 2 configuration file -> the program's ``LlamaConfig``
(three gated delta-rule linear layers to one gated softmax layer
without positional embedding, a sigmoid router over all experts of
which a share is held, one shared expert, the head not tied), and the
program's parameter tree -> the layout
``reference/solar_open2_decoder.py`` reads.  Imported only inside
workers: it imports jax.

How the share is written into the file: ``n_routed_experts`` is the
count of experts this chip HOLDS (published 320, listed under
``reduced``), ``deployment.router_width`` the width the router keeps
(the published 320), ``deployment.experts_held`` the first and the last
expert held; ``vocab_size`` is the slice of the vocabulary held;
``gqa_layers`` is the published list, of which the layers below
``num_hidden_layers`` are run.
"""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "configs", "solar-open2.json")


def layer_kinds(gqa_layers: list, n_layers: int) -> tuple:
    """The shortest period of the first ``n_layers`` layers' kinds: a
    layer on ``gqa_layers`` is a softmax ("full") layer, every other a
    "linear" one."""
    kinds = ["full" if i in gqa_layers else "linear"
             for i in range(n_layers)]
    for n in range(1, n_layers + 1):
        if n_layers % n == 0 and kinds == kinds[:n] * (n_layers // n):
            return tuple(kinds[:n])
    raise AssertionError("unreachable: a list is its own period")


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    share, linear = spec["deployment"], spec["linear_attn_config"]
    first, last = share["experts_held"]
    if spec["use_rope"] or not spec["use_gqa_gate"] \
            or spec["kda_use_full_proj"] or not spec["kda_allow_neg_eigval"] \
            or spec["first_k_dense_replace"] or spec["n_shared_experts"] != 1 \
            or linear["num_kv_heads"] is not None \
            or spec["tie_word_embeddings"]:
        raise ValueError(
            "rotated or ungated softmax layers, a full projection for the "
            "decay, write strengths held under 1, leading dense layers, "
            "more than one shared expert, fewer key than query heads in "
            "the linear layers and a tied head are not what "
            "chipbench/models/solar_open2.py maps")
    if last - first + 1 != spec["n_routed_experts"] \
            or last >= share["router_width"]:
        raise ValueError("experts_held does not name n_routed_experts "
                         "experts of the router's width")
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_width=spec["head_dim"], mlp_dim=spec["moe_intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=False, num_experts=spec["n_routed_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        norm_topk_prob=bool(spec["norm_topk_prob"]),
        router_scoring="sigmoid",
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        router_width=share["router_width"], first_expert=first,
        n_shared_experts=spec["n_shared_experts"], full_rope=False,
        layer_kinds=layer_kinds(spec["gqa_layers"],
                                spec["num_hidden_layers"]),
        linear_heads=linear["num_heads"], linear_head_dim=linear["head_dim"],
        linear_conv=linear["short_conv_kernel_size"],
        # the low-rank pairs' width: the head's (the file's
        # ``assumed.kda_use_full_proj``)
        linear_rank=spec["head_dim"], attn_gate=True)


def reference_layers(params: dict, gqa_layers: list | None = None):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/solar_open2_decoder.py`` names them; ``layer(i)`` takes
    layer ``i`` out of the stack of its kind when asked — a softmax
    layer (on ``gqa_layers``, by default those of
    ``configs/solar-open2.json``) out of ``layers``, a linear one out
    of ``linear_layers``, each the next of its stack.  Nothing is
    re-laid: no layer rotates anything."""
    if gqa_layers is None:
        with open(_FILE) as f:
            gqa_layers = json.load(f)["gqa_layers"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}

    def layer(i: int) -> dict:
        softmax = i in gqa_layers
        before = sum(j in gqa_layers for j in range(i))
        stack = params["layers" if softmax else "linear_layers"]
        at = before if softmax else i - before
        return {names.get(own, own): leaf[at] for own, leaf in stack.items()}

    n_layers = (params["layers"]["ln_attn"].shape[0]
                + params["linear_layers"]["ln_attn"].shape[0])
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["lm_head"]
