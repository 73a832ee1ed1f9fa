"""Configuration files of the dense RMSNorm / RoPE / grouped-query /
SwiGLU decoder family (InternLM2, Mistral) -> the program's
``LlamaConfig``, and the program's parameter tree -> the layout the
plain reference reads.  Imported only inside workers: it imports jax.
"""

from __future__ import annotations


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    if spec["hidden_size"] % spec["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the head count")
    if spec.get("sliding_window") or spec.get("bias"):
        raise ValueError("sliding windows and biases are not computed by "
                         "models/llama.py")
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        dtype=jnp.dtype(dtype),
        tie_embeddings=bool(spec["tie_word_embeddings"]))


def reference_layers(params: dict):
    """The program's stacked tree -> ``(embed, layer(i), norm_f, head)``
    as ``reference/dense_decoder.py`` names them.  ``layer(i)`` slices
    one layer out of the stack when asked, so that a float32 copy of a
    7B-width model never has to exist at once."""
    stacked = params["layers"]
    names = {"ln_attn": "attn_norm", "ln_mlp": "mlp_norm", "wq": "wq",
             "wk": "wk", "wv": "wv", "wo": "wo", "w_gate": "w_gate",
             "w_up": "w_up", "w_down": "w_down"}

    def layer(i: int) -> dict:
        return {ref: stacked[own][i] for own, ref in names.items()}

    n_layers = stacked["wq"].shape[0]
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return params["embed"], layer, n_layers, params["norm_f"], head
