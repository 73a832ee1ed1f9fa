"""The LFM2-8B-A1B configuration file -> the program's ``LlamaConfig``
(leading dense layers that are gated short-convolution layers, then
periods of one softmax layer with an RMSNorm a head on q and k and
three more convolution layers; a sigmoid router whose picks a bias
corrects, the picked scores divided by their sum; no shared expert; the
head tied), and the program's parameter tree -> the layout
``reference/lfm2_decoder.py`` reads.  Imported only inside workers: it
imports jax.

How the cut is written into the file: ``layer_types`` is the published
list, of which the first ``num_hidden_layers`` are run; every other key
is as published.
"""

from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                     "configs", "lfm2-8b-a1b.json")
_KINDS = {"conv": "conv", "full_attention": "full"}


def layer_kinds(layer_types: list, n_layers: int, n_dense: int) -> tuple:
    """The kinds of the first ``n_layers`` layers, in the program's
    names.  The routed run (behind the ``n_dense`` leading layers) has
    to be WHOLE periods, a period reaching from one softmax layer to
    the next: the program scans a run over like periods."""
    kinds = tuple(_KINDS[kind] for kind in layer_types[:n_layers])
    lead, routed = kinds[:n_dense], kinds[n_dense:]
    if set(lead) - {"conv"}:
        raise ValueError(
            "chipbench/models/lfm2.py does not map leading dense layers "
            f"of a kind other than conv: {layer_types[:n_dense]}")
    softmax = [i for i, kind in enumerate(routed) if kind == "full"]
    period = routed[:softmax[1]] if len(softmax) > 1 else routed
    if not softmax or softmax[0] != 0 \
            or routed != period * (len(routed) // len(period)):
        raise ValueError(
            "chipbench/models/lfm2.py does not map a layer_types prefix "
            f"whose routed run is not whole periods: layers {n_dense} to "
            f"{n_layers - 1} are {list(routed)}, a period {list(period)}")
    return kinds


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    refused = {
        "a convolution with bias (conv_bias true)": spec["conv_bias"],
        "a router without its correction bias (use_expert_bias false)":
            not spec["use_expert_bias"],
        "gates left as the scores were (norm_topk_prob false)":
            not spec["norm_topk_prob"],
        "a model_type other than lfm2_moe":
            spec["model_type"] != "lfm2_moe",
    }
    if any(refused.values()):
        raise ValueError(
            "chipbench/models/lfm2.py does not map "
            + "; ".join(what for what, found in refused.items() if found))
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        mlp_dim=spec["moe_intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=True, num_experts=spec["num_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        router_scoring="sigmoid", router_bias=True, norm_topk_prob=True,
        # the program multiplies the gates by it where it is not 1
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        n_dense_layers=spec["num_dense_layers"],
        dense_mlp_dim=spec["intermediate_size"], qk_norm="head",
        full_rope=True,
        layer_kinds=layer_kinds(spec["layer_types"],
                                spec["num_hidden_layers"],
                                spec["num_dense_layers"]),
        conv_L_cache=spec["conv_L_cache"])


def reference_layers(params: dict, layer_types: list | None = None,
                     n_dense: int | None = None):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/lfm2_decoder.py`` names them; ``layer(i)`` takes layer
    ``i`` out of the stack of its run and kind when asked — by
    ``layer_types`` and ``num_dense_layers``, by default those of
    ``configs/lfm2-8b-a1b.json``: a leading dense ``conv`` layer out of
    ``dense_conv_layers``, behind them a ``full_attention`` layer out
    of ``layers`` and a ``conv`` one out of ``conv_layers``, each the
    next of its stack.  Nothing is re-laid: the program rotates a head
    in the same pairs (the first half with the second); the head is the
    embedding, transposed."""
    if layer_types is None:
        with open(_FILE) as f:
            spec = json.load(f)
        layer_types, n_dense = spec["layer_types"], spec["num_dense_layers"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}
    softmax = [kind == "full_attention" for kind in layer_types]

    def layer(i: int) -> dict:
        if i < n_dense:
            stack, at = params["dense_conv_layers"], i
        else:
            before = sum(softmax[n_dense:i])
            stack = params["layers" if softmax[i] else "conv_layers"]
            at = before if softmax[i] else i - n_dense - before
        # a conv layer's output projection lies under the name every
        # mix's has in the program
        own = {**names, **({} if softmax[i] else {"wo": "out_proj"})}
        return {own.get(name, name): leaf[at] for name, leaf in stack.items()}

    n_layers = sum(params[stack]["ln_attn"].shape[0] for stack in (
        "dense_conv_layers", "layers", "conv_layers"))
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["embed"].T
