"""The Olmo Hybrid configuration file -> the program's ``LlamaConfig``
(three gated delta-rule linear layers with ONE decay a head and a
rectangular state to one multi-head softmax layer without positional
embedding, RMSNorm over the whole q and k there, the block's norms on
each sub-layer's output, a dense SwiGLU, the head not tied), and the
program's parameter tree -> the layout
``reference/olmo_hybrid_decoder.py`` reads.  Imported only inside
workers: it imports jax.

``layer_types`` is the published list, of which the first
``num_hidden_layers`` are run.
"""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "configs", "olmo-hybrid-7b.json")
_KINDS = {"linear_attention": "linear", "full_attention": "full"}


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
        "a rotary base (rope_parameters.rope_theta other than null)":
            spec["rope_parameters"]["rope_theta"] is not None,
        "a projection bias (attention_bias)": spec["attention_bias"],
        "write strengths held under 1 (linear_allow_neg_eigval false)":
            not spec["linear_allow_neg_eigval"],
        "fewer key than value heads in the linear layers":
            spec["linear_num_key_heads"] != spec["linear_num_value_heads"],
        "a tied head (tie_word_embeddings)": spec["tie_word_embeddings"],
        "an activation other than silu": spec["hidden_act"] != "silu",
        "hidden_size not a whole number of heads":
            spec["hidden_size"] % spec["num_attention_heads"] != 0,
    }
    if any(refused.values()):
        raise ValueError(
            "chipbench/models/olmo_hybrid.py does not map "
            + "; ".join(what for what, found in refused.items() if found))
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        norm_eps=float(spec["rms_norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=False, qk_norm=True, full_rope=False,
        norm_after=True,
        layer_kinds=layer_kinds(spec["layer_types"],
                                spec["num_hidden_layers"]),
        linear_heads=spec["linear_num_value_heads"],
        linear_head_dim=spec["linear_key_head_dim"],
        linear_value_dim=spec["linear_value_head_dim"],
        linear_conv=spec["linear_conv_kernel_dim"])


def reference_layers(params: dict, layer_types: list | None = None):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/olmo_hybrid_decoder.py`` names them; ``layer(i)`` takes
    layer ``i`` out of the stack of its kind when asked — a
    ``full_attention`` layer (by ``layer_types``, by default those of
    ``configs/olmo-hybrid-7b.json``) out of ``layers``, a
    ``linear_attention`` one out of ``linear_layers``, each the next of
    its stack.  Nothing is re-laid: no layer rotates anything."""
    if layer_types is None:
        with open(_FILE) as f:
            layer_types = json.load(f)["layer_types"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}
    softmax = [kind == "full_attention" for kind in layer_types]

    def layer(i: int) -> dict:
        before = sum(softmax[:i])
        stack = params["layers" if softmax[i] else "linear_layers"]
        at = before if softmax[i] else i - before
        return {names.get(own, own): leaf[at] for own, leaf in stack.items()}

    n_layers = (params["layers"]["ln_attn"].shape[0]
                + params["linear_layers"]["ln_attn"].shape[0])
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["lm_head"]
