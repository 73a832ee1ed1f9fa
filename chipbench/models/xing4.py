"""The Xing4.0-29B-A4B configuration file -> the program's
``LlamaConfig`` (four residual streams a token under manifold-constrained
hyper-connections, latent attention, YaRN, a sigmoid router with
``noaux_tc``'s correction bias over experts that are ALL held, a shared
expert, leading dense layers), and the program's parameter tree -> the
layout ``reference/xing4_decoder.py`` reads.  Imported only inside
workers: it imports jax.
"""

from __future__ import annotations

# the tree's layout is A.X-K1's (two stacks, the rotary columns put back
# in the published order); the maps' and the bias's leaves keep their names
from chipbench.models.axk1 import reference_layers  # noqa: F401


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig
    from ant_ray_tpu.ops.rope import YarnScaling

    yarn, share = spec["rope_scaling"], spec["deployment"]
    refused = {
        "group-limited selection (n_group or topk_group above 1)":
            spec["n_group"] > 1 or spec["topk_group"] > 1,
        "a topk_method other than noaux_tc":
            spec["topk_method"] != "noaux_tc",
        "a share of the experts (experts_held other than all of "
        "n_routed_experts)": share["experts_held"] != [
            0, spec["n_routed_experts"] - 1]
            or share["router_width"] != spec["n_routed_experts"],
        "an attention bias": bool(spec.get("attention_bias")),
        "a rope scaling other than yarn": yarn["type"] != "yarn",
        "dense layers between routed ones (moe_layer_freq other than 1)":
            spec["moe_layer_freq"] != 1,
        "an activation other than silu": spec["hidden_act"] != "silu",
        "a scoring function other than sigmoid":
            spec["scoring_func"] != "sigmoid",
    }
    if any(refused.values()):
        raise ValueError(
            "chipbench/models/xing4.py does not map "
            + "; ".join(what for what, found in refused.items() if found))
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        mlp_dim=spec["moe_intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        dtype=jnp.dtype(dtype),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        num_experts=spec["n_routed_experts"],
        experts_per_token=spec["num_experts_per_tok"],
        norm_topk_prob=bool(spec["norm_topk_prob"]),
        router_scoring=spec["scoring_func"], router_bias=True,
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        n_shared_experts=spec["n_shared_experts"],
        n_dense_layers=spec["first_k_dense_replace"],
        dense_mlp_dim=spec["intermediate_size"],
        q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_head_dim=spec["qk_nope_head_dim"],
        qk_rope_head_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"],
        rope_scaling=YarnScaling(
            factor=float(yarn["factor"]),
            original_max_position_embeddings=yarn[
                "original_max_position_embeddings"],
            beta_fast=float(yarn["beta_fast"]),
            beta_slow=float(yarn["beta_slow"]),
            mscale=float(yarn["mscale"]),
            mscale_all_dim=float(yarn["mscale_all_dim"])),
        hc_mult=spec["hc_mult"],
        hc_sinkhorn_iters=spec["hc_sinkhorn_iters"],
        hc_eps=float(spec["hc_eps"]),
        hc_res_clamp=(float(spec["mhc_h_res_clamp_min"]),
                      float(spec["mhc_h_res_clamp_max"])))
