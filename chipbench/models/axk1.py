"""The A.X-K1 configuration file -> the program's ``LlamaConfig`` (latent
attention, YaRN, a sigmoid router over all experts of which a share is
held, a shared expert, leading dense layers), and the program's
parameter tree -> the layout ``reference/axk1_decoder.py`` reads.
Imported only inside workers: it imports jax.

How the share is written into the file: ``n_routed_experts`` is the
count of experts this chip HOLDS (published 192, listed under
``reduced``), ``deployment.router_width`` the width the router keeps
(the published 192), ``deployment.experts_held`` the first and the last
expert held; ``vocab_size`` is the slice of the vocabulary held.
"""

from __future__ import annotations


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig
    from ant_ray_tpu.ops.rope import YarnScaling

    yarn, share = spec["rope_scaling"], spec["deployment"]
    first, last = share["experts_held"]
    if spec.get("attention_bias") or yarn["type"] != "yarn" \
            or spec["moe_layer_freq"] != 1 or spec["hidden_act"] != "silu" \
            or spec["topk_method"] != "none":
        raise ValueError("biases, rope scalings other than yarn, dense "
                         "layers between routed ones and group-limited or "
                         "bias-corrected routing are not computed by "
                         "models/llama.py")
    if last - first + 1 != spec["n_routed_experts"] \
            or last >= share["router_width"]:
        raise ValueError("experts_held does not name n_routed_experts "
                         "experts of the router's width")
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
        router_scoring=spec["scoring_func"],
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        router_width=share["router_width"], first_expert=first,
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
            mscale_all_dim=float(yarn["mscale_all_dim"])))


def reference_layers(params: dict):
    """The program's tree -> ``(embed, layer(i), n, norm_f, head)`` as
    ``reference/axk1_decoder.py`` names them; ``layer(i)`` takes one
    layer out of its stack when asked — the leading dense layers, then
    the routed ones.

    ONE thing is re-laid: the program rotates a head's rotary dimensions
    in the half-split order (pairs (j, j + rope/2), ``ops/rope.py``),
    the published weights and the reference in pairs (2j, 2j + 1).  The
    program's random weights are read as already permuted, so the
    reference gets the rotary columns of ``w_qb`` (every head's last
    ``rope``) and of ``w_kva`` (its last ``rope``) put back in the
    published order: the inverse of
    ``rope.half_split_from_interleaved``, which a loader of published
    weights would apply.  Scores are unchanged by it, so both sides
    compute the same function of the same numbers."""
    import jax.numpy as jnp

    from ant_ray_tpu.ops.rope import half_split_from_interleaved

    stacks = [params[name] for name in ("dense_layers", "layers")
              if name in params]
    rank = stacks[0]["kv_a_norm"].shape[-1]
    rope = stacks[0]["w_kva"].shape[-1] - rank
    published = jnp.argsort(half_split_from_interleaved(rope))
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm"}

    def rotary_columns_published(w, heads_of):
        """w (in, heads * width): each head's last ``rope`` columns."""
        by_head = w.reshape(w.shape[0], heads_of, -1)
        tail = by_head[..., -rope:][..., published]
        return jnp.concatenate([by_head[..., :-rope], tail],
                               axis=-1).reshape(w.shape)

    def layer(i: int) -> dict:
        for stack in stacks:
            n = stack["ln_attn"].shape[0]
            if i < n:
                break
            i -= n
        out = {names.get(own, own): leaf[i] for own, leaf in stack.items()}
        out["w_kva"] = rotary_columns_published(out["w_kva"], 1)
        out["w_qb"] = rotary_columns_published(out["w_qb"],
                                               _heads(out, rope))
        return out

    n_layers = sum(stack["ln_attn"].shape[0] for stack in stacks)
    return params["embed"], layer, n_layers, params["norm_f"], \
        params["lm_head"]


def _heads(layer: dict, rope: int) -> int:
    """Heads, from the shapes: ``w_qb`` is heads * (nope + rope) wide,
    ``w_kvb`` heads * (nope + v), ``wo`` heads * v deep."""
    return (layer["w_qb"].shape[1] - layer["w_kvb"].shape[1]
            + layer["wo"].shape[0]) // rope
