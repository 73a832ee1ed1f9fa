"""The OLMoE configuration file -> the program's ``LlamaConfig`` (routed
experts, gates not renormalised, QK-norm), and the program's parameter
tree -> the layout ``reference/olmoe_decoder.py`` reads.  Imported only
inside workers: it imports jax.
"""

from __future__ import annotations


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names):
    the dense decoder's fields, plus the router's and the QK-norm."""
    import dataclasses

    from chipbench.models import dense_llama

    if spec.get("attention_bias") or spec.get("clip_qkv") is not None \
            or spec.get("rope_scaling") is not None:
        raise ValueError("biases, clip_qkv and rope scaling are not "
                         "computed by models/llama.py")
    return dataclasses.replace(
        dense_llama.build(spec, dtype=dtype),
        num_experts=spec["num_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        norm_topk_prob=bool(spec["norm_topk_prob"]),
        qk_norm=True)


def reference_layers(params: dict):
    """The program's stacked tree -> ``(embed, layer(i), norm_f, head)``
    as ``reference/olmoe_decoder.py`` names them.  ``layer(i)`` slices
    one layer out of the stack when asked."""
    stacked = params["layers"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}

    def layer(i: int) -> dict:
        return {names.get(own, own): leaf[i] for own, leaf in stacked.items()}

    n_layers = stacked["wq"].shape[0]
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return params["embed"], layer, n_layers, params["norm_f"], head
