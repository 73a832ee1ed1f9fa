"""The Ouro configuration file -> the program's ``LlamaConfig`` (one
stack of like dense multi-head layers with sandwich norms that a token
passes through ``total_ut_steps`` times, the final norm between the
passes, the exit gate, the head not tied), and the program's parameter
tree -> the layout ``reference/ouro_decoder.py`` reads.  Imported only
inside workers: it imports jax.
"""

from __future__ import annotations


def build(spec: dict, *, dtype: str = "bfloat16"):
    """``spec`` is a file of ``chipbench/configs`` (published key names)."""
    import dataclasses

    import jax.numpy as jnp

    from ant_ray_tpu.models.llama import LlamaConfig

    lacking = {"loops", "sandwich_norm", "exit_gate"} - {
        field.name for field in dataclasses.fields(LlamaConfig)}
    if lacking:
        raise ValueError(
            "chipbench/models/ouro.py: this program's LlamaConfig has no "
            + ", ".join(sorted(lacking)) + ": it runs no looped model")
    refused = {
        "an early_exit_threshold other than 1 (a row that leaves the loop "
        "early is not computed: a step program runs every row through "
        "every pass)": float(spec["early_exit_threshold"]) != 1.0,
        "use_sliding_window true": bool(spec["use_sliding_window"]),
        "a rope_scaling": spec["rope_scaling"] is not None,
        "tied embeddings (tie_word_embeddings)":
            bool(spec["tie_word_embeddings"]),
        "layer_types other than all full_attention":
            set(spec["layer_types"]) != {"full_attention"}
            or len(spec["layer_types"]) != spec["num_hidden_layers"],
        "an activation other than silu": spec["hidden_act"] != "silu",
    }
    if any(refused.values()):
        raise ValueError(
            "chipbench/models/ouro.py does not map "
            + "; ".join(what for what, found in refused.items() if found))
    return LlamaConfig(
        vocab_size=spec["vocab_size"], dim=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_width=spec["head_dim"], mlp_dim=spec["intermediate_size"],
        max_seq=spec["max_position_embeddings"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]), dtype=jnp.dtype(dtype),
        tie_embeddings=False, sandwich_norm=True,
        loops=spec["total_ut_steps"], exit_gate=True)


def reference_layers(params: dict):
    """The program's stacked tree -> ``(embed, layer(i), n, closing,
    head)`` as ``reference/ouro_decoder.py`` names them: ``layer(i)``
    slices one layer out of the stack when asked, ``closing`` is what
    ends a pass — the final norm and the exit gate."""
    stacked = params["layers"]
    names = {"ln_attn": "attn_norm", "ln_attn_out": "attn_out_norm",
             "ln_mlp": "mlp_norm", "ln_mlp_out": "mlp_out_norm"}

    def layer(i: int) -> dict:
        return {names.get(own, own): leaf[i] for own, leaf in stacked.items()}

    gate = params["exit_gate"]
    closing = {"norm_f": params["norm_f"], "gate_w": gate["w"],
               "gate_b": gate["b"]}
    return (params["embed"], layer, stacked["wq"].shape[0], closing,
            params["lm_head"])
