"""The Granite 4.0-H configuration file -> the program's ``LlamaConfig``
(nine Mamba-2 state-space layers to one softmax layer without
positional embedding, a softmax router over all experts of which a
share is held, one shared SwiGLU, four scalar multipliers, the head
tied), and the program's parameter tree -> the layout
``reference/granite_hybrid_decoder.py`` reads.  Imported only inside
workers: it imports jax.

How the share is written into the file: ``num_local_experts`` is the
count of experts this chip HOLDS (published 72, listed under
``reduced``), ``deployment.router_width`` the width the router keeps
(the published 72), ``deployment.experts_held`` the first and the last
expert held; ``vocab_size`` is the slice of the vocabulary held;
``layer_types`` is the published list, of which the first
``num_hidden_layers`` are run.
"""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "configs", "granite-4.0-h-small.json")
_KINDS = {"mamba": "ssm", "attention": "full"}


def layer_kinds(layer_types: list, n_layers: int) -> tuple:
    """The shortest period of the first ``n_layers`` layers' kinds, in
    the program's names."""
    kinds = [_KINDS[kind] for kind in layer_types[:n_layers]]
    for n in range(1, n_layers + 1):
        if n_layers % n == 0 and kinds == kinds[:n] * (n_layers // n):
            return tuple(kinds[:n])
    raise AssertionError("unreachable: a list is its own period")


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    refused = {
        "mamba_n_groups other than 1 (B and C a group their own)":
            spec["mamba_n_groups"] != 1,
        "a projection bias (mamba_proj_bias, attention_bias)":
            spec["mamba_proj_bias"] or spec["attention_bias"],
        "a convolution without bias (mamba_conv_bias false)":
            not spec["mamba_conv_bias"],
        "rotary softmax layers (position_embedding_type other than nope)":
            spec["position_embedding_type"] != "nope",
        "an untied head (tie_word_embeddings false)":
            not spec["tie_word_embeddings"],
        "an activation other than silu": spec["hidden_act"] != "silu",
        "a norm other than rmsnorm":
            spec["normalization_function"] != "rmsnorm",
        "mamba_expand * hidden_size other than mamba_n_heads * "
        "mamba_d_head": spec["mamba_expand"] * spec["hidden_size"]
            != spec["mamba_n_heads"] * spec["mamba_d_head"],
        "a shared expert that is not a whole number of experts wide":
            spec["shared_intermediate_size"] % spec["intermediate_size"] != 0,
    }
    if any(refused.values()):
        raise ValueError(
            "chipbench/models/granite_hybrid.py does not map "
            + "; ".join(what for what, found in refused.items() if found))
    share = spec["deployment"]
    first, last = share["experts_held"]
    if last - first + 1 != spec["num_local_experts"] \
            or last >= share["router_width"]:
        raise ValueError("experts_held does not name num_local_experts "
                         "experts of the router's width")
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=True, num_experts=spec["num_local_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        # a softmax over the k picked logits is the softmax over all of
        # them divided by the k's sum
        router_scoring="softmax", norm_topk_prob=True,
        router_width=share["router_width"], first_expert=first,
        n_shared_experts=spec["shared_intermediate_size"]
        // spec["intermediate_size"], full_rope=False,
        layer_kinds=layer_kinds(spec["layer_types"],
                                spec["num_hidden_layers"]),
        ssm_heads=spec["mamba_n_heads"], ssm_head_dim=spec["mamba_d_head"],
        ssm_state=spec["mamba_d_state"], ssm_groups=spec["mamba_n_groups"],
        ssm_conv=spec["mamba_d_conv"],
        embedding_multiplier=float(spec["embedding_multiplier"]),
        residual_multiplier=float(spec["residual_multiplier"]),
        attention_multiplier=float(spec["attention_multiplier"]),
        logits_scaling=float(spec["logits_scaling"]))


def reference_layers(params: dict, layer_types: list | None = None):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/granite_hybrid_decoder.py`` names them; ``layer(i)``
    takes layer ``i`` out of the stack of its kind when asked — an
    ``attention`` layer (by ``layer_types``, by default those of
    ``configs/granite-4.0-h-small.json``) out of ``layers``, a ``mamba``
    one out of ``ssm_layers``, each the next of its stack.  Nothing is
    re-laid: no layer rotates anything; the head is the embedding,
    transposed."""
    if layer_types is None:
        with open(_FILE) as f:
            layer_types = json.load(f)["layer_types"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}
    softmax = [kind == "attention" for kind in layer_types]

    def layer(i: int) -> dict:
        before = sum(softmax[:i])
        stack = params["layers" if softmax[i] else "ssm_layers"]
        at = before if softmax[i] else i - before
        # a mamba layer's output projection lies under the name every
        # mix's has in the program
        own = {**names, **({} if softmax[i] else {"wo": "out_proj"})}
        return {own.get(name, name): leaf[at] for name, leaf in stack.items()}

    n_layers = (params["layers"]["ln_attn"].shape[0]
                + params["ssm_layers"]["ln_attn"].shape[0])
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["embed"].T
