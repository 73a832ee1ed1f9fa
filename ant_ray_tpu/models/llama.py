"""Llama model family — functional JAX, one definition for every
parallelism strategy.

Design: parameters are a plain pytree with a parallel tree of *logical*
dimension names (parallel/sharding.py) so DP / FSDP / TP / SP placement is
a rule-table swap, not a model change.  Layers are stacked on a leading
axis and executed with ``lax.scan`` (fast compiles, uniform remat), blocks
are ``jax.checkpoint``-ed, attention dispatches to blockwise / pallas
flash / ring (sequence-parallel) based on the mesh.

Flagship configs mirror the reference's north-star benchmark target
(BASELINE.md: Llama-3-8B ≥ 40% MFU on v5e-64).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ant_ray_tpu.ops.attention import attention, kernel_fits
from ant_ray_tpu.ops.rmsnorm import rmsnorm
from ant_ray_tpu.ops.rope import apply_rope, rope_frequencies
from ant_ray_tpu.parallel.sharding import logical_to_spec


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    # Mixture-of-experts MLP (0 = dense): ``_routed_mlp`` sorts the
    # (token, expert) assignments by expert and runs one grouped product
    # over them, so the work grows with ``experts_per_token``, not with
    # ``num_experts``.  Experts shard over the mesh's ``ep`` axis.
    num_experts: int = 0
    experts_per_token: int = 2
    # Gates are the top-k of a softmax over ALL experts; True divides
    # them by their sum (Mixtral), False leaves them as they are (OLMoE).
    norm_topk_prob: bool = True
    # RMSNorm over the whole q and k projections, before RoPE (OLMoE).
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        p = self.vocab_size * self.dim                       # embed
        if self.num_experts:
            mlp = (self.dim * self.num_experts               # router
                   + 3 * self.num_experts * self.dim * self.mlp_dim)
        else:
            mlp = 3 * self.dim * self.mlp_dim                # gate, up, down
        per_layer = (
            self.dim * self.n_heads * self.head_dim          # wq
            + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.dim        # wo
            + mlp
            + 2 * self.dim                                   # norms
            + ((self.n_heads + self.n_kv_heads) * self.head_dim
               if self.qk_norm else 0)                       # q, k norms
        )
        p += self.n_layers * per_layer + self.dim            # final norm
        if not self.tie_embeddings:
            p += self.dim * self.vocab_size                  # lm head
        return p


CONFIGS: dict[str, LlamaConfig] = {
    # ref parity: the Llama-3-8B benchmark model (BASELINE.md north star)
    "llama3-8b": LlamaConfig(),
    "llama3-1b": LlamaConfig(
        vocab_size=128256, dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
        mlp_dim=8192, max_seq=8192),
    # small enough to train on one v5e chip (bench fallback).
    # head_dim=128 (not 64): the MXU contracts 128 lanes per pass, so
    # 64-deep attention matmuls run the array half-empty — measured 1.8×
    # slower end-to-end.  Matches Llama-3's head_dim at every scale.
    "llama-400m": LlamaConfig(
        vocab_size=32768, dim=1024, n_layers=24, n_heads=8, n_kv_heads=4,
        mlp_dim=4096, max_seq=4096),
    "tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq=512, dtype=jnp.float32),
    # MoE variant: 4 experts, top-2 routing — the ep-axis test model
    "moe-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq=512, dtype=jnp.float32,
        num_experts=4, experts_per_token=2),
    # OLMoE's block at test size: 8 experts, 2 a token, gates left as
    # the softmax gave them, RMSNorm over the whole q and k projections
    "olmoe-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq=512, dtype=jnp.float32,
        num_experts=8, experts_per_token=2, norm_topk_prob=False,
        qk_norm=True),
}


# ---------------------------------------------------------------- params

def param_shapes(config: LlamaConfig) -> dict:
    c = config
    hd = c.head_dim
    if c.num_experts:
        mlp_shapes = {
            "router": (c.n_layers, c.dim, c.num_experts),
            "w_gate": (c.n_layers, c.num_experts, c.dim, c.mlp_dim),
            "w_up": (c.n_layers, c.num_experts, c.dim, c.mlp_dim),
            "w_down": (c.n_layers, c.num_experts, c.mlp_dim, c.dim),
        }
    else:
        mlp_shapes = {
            "w_gate": (c.n_layers, c.dim, c.mlp_dim),
            "w_up": (c.n_layers, c.dim, c.mlp_dim),
            "w_down": (c.n_layers, c.mlp_dim, c.dim),
        }
    return {
        "embed": (c.vocab_size, c.dim),
        "layers": {
            "ln_attn": (c.n_layers, c.dim),
            "wq": (c.n_layers, c.dim, c.n_heads * hd),
            "wk": (c.n_layers, c.dim, c.n_kv_heads * hd),
            "wv": (c.n_layers, c.dim, c.n_kv_heads * hd),
            "wo": (c.n_layers, c.n_heads * hd, c.dim),
            "ln_mlp": (c.n_layers, c.dim),
            **mlp_shapes,
            **({"q_norm": (c.n_layers, c.n_heads * hd),
                "k_norm": (c.n_layers, c.n_kv_heads * hd)}
               if c.qk_norm else {}),
        },
        "norm_f": (c.dim,),
        **({} if config.tie_embeddings else
           {"lm_head": (c.dim, c.vocab_size)}),
    }


def param_logical_dims(config: LlamaConfig) -> dict:
    """Logical dim names per param (see parallel/sharding.py rules)."""
    if config.num_experts:
        mlp_dims = {
            "router": (None, None, "experts"),
            "w_gate": (None, "experts", "embed_param", "mlp"),
            "w_up": (None, "experts", "embed_param", "mlp"),
            "w_down": (None, "experts", "mlp", "embed_param"),
        }
    else:
        mlp_dims = {
            "w_gate": (None, "embed_param", "mlp"),
            "w_up": (None, "embed_param", "mlp"),
            "w_down": (None, "mlp", "embed_param"),
        }
    tree = {
        "embed": ("vocab", "embed_param"),
        "layers": {
            "ln_attn": (None, "norm"),
            "wq": (None, "embed_param", "heads_flat"),
            "wk": (None, "embed_param", "heads_flat"),
            "wv": (None, "embed_param", "heads_flat"),
            "wo": (None, "heads_flat", "embed_param"),
            "ln_mlp": (None, "norm"),
            **mlp_dims,
            **({"q_norm": (None, "norm"), "k_norm": (None, "norm")}
               if config.qk_norm else {}),
        },
        "norm_f": ("norm",),
    }
    if not config.tie_embeddings:
        tree["lm_head"] = ("embed_param", "vocab")
    return tree


# extra rule: flattened (heads*head_dim) dims shard over tp
LLAMA_RULES_EXTRA = {"heads_flat": "tp"}


def llama_rules() -> dict:
    from ant_ray_tpu.parallel.sharding import DEFAULT_LLAMA_RULES  # noqa: PLC0415

    rules = dict(DEFAULT_LLAMA_RULES)
    rules.update(LLAMA_RULES_EXTRA)
    return rules


def init_params(config: LlamaConfig, key) -> dict:
    def is_leaf(x):
        return isinstance(x, tuple)

    flat, treedef = jax.tree.flatten(param_shapes(config), is_leaf=is_leaf)
    dims = jax.tree.leaves(param_logical_dims(config), is_leaf=is_leaf)
    keys = jax.random.split(key, len(flat))

    def _init(shape, logical, k):
        if logical[-1] == "norm":
            return jnp.ones(shape, config.dtype)
        scale = 0.02
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            config.dtype)

    leaves = [_init(s, d, k) for s, d, k in zip(flat, dims, keys)]
    return jax.tree.unflatten(treedef, leaves)


def param_shardings(config: LlamaConfig, mesh) -> dict:
    """NamedSharding pytree for jit in_shardings / device_put."""
    from jax.sharding import NamedSharding  # noqa: PLC0415

    rules = llama_rules()
    logical = param_logical_dims(config)
    shapes = param_shapes(config)

    def _shard(dims, _shape):
        return NamedSharding(mesh, logical_to_spec(dims, rules))

    return jax.tree.map(
        _shard, logical, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(d, (str, type(None))) for d in x))


# ---------------------------------------------------------------- forward

def apply_block(layer: dict, x, c: LlamaConfig, cos, sin, positions,
                attend, constrain_act, index=None):
    """One transformer block on ``x`` (..., dim), the only place its
    equations are written: training hands it (batch, seq, dim), a
    prefill chunk and a decode step their rows, (chunk, dim) and
    (slots, dim).  The three differ in how they attend:
    ``attend(xq, xk, xv) -> (out, state)`` takes rotated queries and
    keys and the values, heads split, and returns the attention output,
    shaped as the queries, beside what the caller keeps of the layer —
    nothing, its (xk, xv), or the KV cache with its rows written
    (``_scan_layers``).  ``positions``: int32 of ``x``'s leading shape,
    None for arange over the sequence.  ``index``: the layer's number
    where ``layer`` holds the whole stack's expert matrices
    (``_routed_mlp``).  Returns ``(x, state, load)``, ``load`` as
    ``_mlp`` gives it."""
    lead = x.shape[:-1]
    h = rmsnorm(x, layer["ln_attn"], c.norm_eps)
    xq, xk = _qk_proj(layer, h, c)
    xq = xq.reshape(*lead, c.n_heads, c.head_dim)
    xk = xk.reshape(*lead, c.n_kv_heads, c.head_dim)
    xv = (h @ layer["wv"]).reshape(*lead, c.n_kv_heads, c.head_dim)
    xq = apply_rope(xq, cos, sin, positions)
    xk = apply_rope(xk, cos, sin, positions)
    xq = constrain_act(xq, ("batch", "seq", "heads", "head_dim"))
    xk = constrain_act(xk, ("batch", "seq", "kv_heads", "head_dim"))
    attn, state = attend(xq, xk, xv)
    attn = attn.reshape(*lead, c.n_heads * c.head_dim)
    x = x + (attn @ layer["wo"]).astype(x.dtype)
    x = constrain_act(x, ("batch", "seq", "embed"))

    h = rmsnorm(x, layer["ln_mlp"], c.norm_eps)
    out, load = _mlp(layer, h, c, index)
    x = x + out.astype(x.dtype)
    x = constrain_act(x, ("batch", "seq", "embed"))
    return x, state, load


def _unconstrained(x, _dims):
    """``apply_block``'s ``constrain_act`` where no mesh lays the
    activations out."""
    return x


def _qk_proj(layer: dict, h, c: LlamaConfig):
    """The q and k projections of ``h`` (..., dim), still flat
    (..., heads * head_dim): with ``qk_norm`` each is RMS-normalised
    over its WHOLE width — all heads together, as OLMoE publishes it,
    not head by head — before the caller splits heads and applies RoPE.
    The one place q/k are made, for training, chunks and decode."""
    xq, xk = h @ layer["wq"], h @ layer["wk"]
    if c.qk_norm:
        xq = rmsnorm(xq, layer["q_norm"], c.norm_eps)
        xk = rmsnorm(xk, layer["k_norm"], c.norm_eps)
    return xq, xk


def _mlp(layer: dict, h, c: LlamaConfig, index=None):
    """The block's feed-forward on ``h`` (..., dim): dense SwiGLU, or
    the routed experts.  Returns ``(out, load)``; ``load`` is the
    (num_experts,) int32 count of rows each expert was given, None for
    a dense model.  The one MLP of training, chunks and decode."""
    if c.num_experts:
        return _routed_mlp(layer, h, c, index)
    gated = jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])
    return gated @ layer["w_down"], None


def _routed_mlp(layer: dict, h, c: LlamaConfig, index=None):
    """Top-k mixture of experts; every token is computed by its k
    experts only, and none is dropped.

    The router's probabilities are a float32 softmax over ALL experts;
    the gates are its k largest (divided by their sum only when
    ``norm_topk_prob``).  The tokens * k (token, expert) assignments are
    sorted by expert, so each expert's rows lie together, and
    ``lax.ragged_dot`` multiplies each run of rows with its expert's
    matrix: a grouped product whose operations are those of k experts a
    token, and which reads an expert's weights only if it has a row.
    On the TPU XLA compiles it to a grouped-matmul kernel; elsewhere to
    masked dense products (the same values).  The rows then go back to
    token order and are summed under their float32 gates.  Shapes are
    static (tokens * k rows whatever the routing), so one formulation
    serves the training step, a prefill chunk and a decode step, and
    differentiates as written.  With experts sharded over ``ep`` the
    partitioner splits the grouped product by expert.

    ``layer``'s expert matrices are one layer's (experts, in, out), as
    a scan over the stacked layers slices them — or, with ``index``,
    the whole stack's (layers, experts, in, out), of which layer
    ``index`` (traced) is meant: the stack is then read as layers *
    experts groups, all empty but that layer's.  The step programs do
    so (``_scan_layers``), because a slice of the stack cannot be fused
    into the grouped kernel's operand: sliced, every step would first
    copy every expert's weights, hit or not.
    """
    lead, dim = h.shape[:-1], h.shape[-1]
    k, n_exp = c.experts_per_token, c.num_experts
    with jax.named_scope("moe"):
        x = h.reshape(-1, dim)
        probs = jax.nn.softmax(jnp.dot(
            x, layer["router"], preferred_element_type=jnp.float32), axis=-1)
        gates, experts = lax.top_k(probs, k)               # (tokens, k)
        if c.norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        experts = experts.reshape(-1)                      # (tokens * k,)
        order = jnp.argsort(experts)                       # stable
        load = jnp.zeros((n_exp,), jnp.int32).at[experts].add(1)
        rows = x[order // k]                               # sorted by expert
        sizes = load if index is None else lax.dynamic_update_slice(
            jnp.zeros((layer["w_down"].shape[0] * n_exp,), jnp.int32),
            load, (index * n_exp,))

        def grouped(a, w):
            return lax.ragged_dot(a, w.reshape(-1, *w.shape[-2:]), sizes,
                                  preferred_element_type=jnp.float32)

        gated = jax.nn.silu(grouped(rows, layer["w_gate"])) * grouped(
            rows, layer["w_up"])
        out = grouped(gated.astype(h.dtype), layer["w_down"])
        out = out[jnp.argsort(order)].reshape(-1, k, dim)  # token order
        out = jnp.sum(out * gates[..., None], axis=1)
    return out.astype(h.dtype).reshape(*lead, dim), load


def forward(params: dict, tokens, config: LlamaConfig, *, mesh=None,
            attn_impl: str = "auto", positions=None,
            return_kv: bool = False, logits_at=None,
            remat: str = "full"):
    """tokens: (batch, seq) int32 → logits (batch, seq, vocab) fp32.

    When ``mesh`` is provided, activations get sharding constraints
    (batch over dp/fsdp, seq over sp, heads over tp) and sequence-sharded
    meshes use ring attention.

    ``return_kv=True`` additionally returns the per-layer K/V
    (layers, b, s, kv_heads, hd), which the block's attention hands back
    as its state, for the bucketed prefill (``prefill_into_cache``) to
    put into a slot;
    ``logits_at`` (traced scalar position) computes logits for that one
    position only — (b, vocab) — skipping the full-sequence lm-head
    matmul.

    ``remat`` trades HBM for recompute FLOPs in the backward pass:
    "full" (checkpoint every block — the multi-chip/8B default), "dots"
    (save matmul outputs, recompute the cheap elementwise tail), "none"
    (save everything — best MFU when the model fits, e.g. the single-chip
    bench).
    """
    c = config
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta,
                                jnp.float32)
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1

    def constrain_act(x, dims):
        if mesh is None:
            return x
        from jax.sharding import NamedSharding  # noqa: PLC0415

        spec = logical_to_spec(dims, llama_rules())
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def attend(xq, xk, xv):
        # no cache: the whole sequence attends over itself
        if use_ring:
            from ant_ray_tpu.parallel.ring import ring_attention  # noqa: PLC0415

            out = ring_attention(xq, xk, xv, mesh=mesh, causal=True)
        elif mesh is None:
            out = attention(xq, xk, xv, causal=True, impl=attn_impl)
        else:
            rules = llama_rules()
            out = attention(
                xq, xk, xv, causal=True, impl=attn_impl, mesh=mesh,
                q_spec=logical_to_spec(
                    ("batch", "seq", "heads", "head_dim"), rules),
                kv_spec=logical_to_spec(
                    ("batch", "seq", "kv_heads", "head_dim"), rules))
        kv = (xk.astype(c.dtype), xv.astype(c.dtype)) if return_kv else None
        return out, kv

    def block(x, layer):
        x, kv, _ = apply_block(layer, x, c, cos, sin, positions, attend,
                               constrain_act)
        return x, kv

    if remat == "full":
        block = jax.checkpoint(block)
    elif remat == "dots":
        block = jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat == "matmuls":
        # Saves every matmul output (batch dims included) plus the flash
        # kernel's named residuals (attention output + logsumexp) — in a
        # transformer block that is all the expensive ops, so backward
        # recomputes only the elementwise tail and never re-runs the
        # attention forward.  ~3× the activation HBM of "full",
        # near-"none" step time; the single-chip bench sweet spot when
        # "none" OOMs.
        from ant_ray_tpu.ops.attention import saveable_attention_policy  # noqa: PLC0415

        block = jax.checkpoint(block, policy=saveable_attention_policy())
    elif remat != "none":
        raise ValueError(f"unknown remat policy {remat!r}")

    x = params["embed"][tokens].astype(c.dtype)
    # Staged reshard: first acknowledge the gather's TABLE-natural
    # output sharding (embed dim carries the table's fsdp shards; batch
    # keeps its dp shard — fsdp moves from batch to embed for one hop),
    # then relayout to the activation spec.  One constraint straight to
    # the target makes SPMD fall back to "involuntary full
    # rematerialization" (replicate-everything) on the sp/tp meshes;
    # the explicit intermediate lets it emit a plain all-gather +
    # dynamic-slice.  Spec built directly: the logical rule table can't
    # say "batch over dp only".
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec  # noqa: PLC0415

        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec("dp", "sp", "fsdp")))
    x = constrain_act(x, ("batch", "seq", "embed"))
    x, kv = lax.scan(block, x, params["layers"])
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    if logits_at is not None:
        x = jnp.take(x, logits_at, axis=1)          # (b, dim)
        logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
    else:
        logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
        logits = constrain_act(logits, ("batch", "seq", None))
    if return_kv:
        return logits, kv[0], kv[1]
    return logits


def loss_fn(params: dict, batch: dict, config: LlamaConfig, *, mesh=None,
            attn_impl: str = "auto", remat: str = "full"):
    """batch: {"tokens": (b, s+1) int32} — next-token cross entropy."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward(params, inputs, config, mesh=mesh, attn_impl=attn_impl,
                     remat=remat)
    import optax  # noqa: PLC0415

    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(losses)


def loss_fn_pp(params: dict, batch: dict, config: LlamaConfig, *, mesh,
               num_microbatches: int = 4, attn_impl: str = "auto"):
    """Pipeline-parallel next-token loss: the transformer blocks run as a
    GPipe schedule over the mesh's ``pp`` axis (parallel/pipeline.py —
    single compiled program, activations hop stages via ppermute),
    composing with dp/fsdp/tp on the remaining axes.  Requires
    n_layers % pp == 0 and batch % num_microbatches == 0."""
    from ant_ray_tpu.parallel.pipeline import gpipe  # noqa: PLC0415

    c = config
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    pp = mesh.shape["pp"]
    if c.n_layers % pp != 0:
        raise ValueError(f"n_layers {c.n_layers} % pp {pp} != 0")
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta,
                                jnp.float32)

    def attend(xq, xk, xv):
        return attention(xq, xk, xv, causal=True, impl=attn_impl), None

    def stage_fn(stage_layers, mx):
        def body(h, layer):
            h, _, _ = apply_block(layer, h, c, cos, sin, None, attend,
                                  _unconstrained)
            return h, None

        out, _ = lax.scan(body, mx, stage_layers)
        return out

    x = params["embed"][inputs].astype(c.dtype)          # (b, s, d)
    b = x.shape[0]
    if b % num_microbatches != 0:
        raise ValueError(
            f"batch {b} % microbatches {num_microbatches} != 0")
    micro = x.reshape(num_microbatches, b // num_microbatches,
                     *x.shape[1:])
    stacked = jax.tree.map(
        lambda p: p.reshape(pp, c.n_layers // pp, *p.shape[1:]),
        params["layers"])
    y = gpipe(stage_fn, stacked, micro, mesh=mesh)
    x = y.reshape(b, *y.shape[2:])
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
    import optax  # noqa: PLC0415

    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(logits, targets))


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token (6·N matmul + attention quadratic term); of
    a routed model's experts N holds the k a token multiplies with."""
    c = config
    idle = max(c.num_experts - c.experts_per_token, 0)
    matmul = 6 * (c.num_params()
                  - c.n_layers * idle * 3 * c.dim * c.mlp_dim)
    attn = 12 * c.n_layers * c.head_dim * c.n_heads * seq_len
    return matmul + attn


# ------------------------------------------------------------- kv cache
# Serving-path primitives (ref capability: llm/_internal/serve/engines/
# vllm — re-designed TPU-first: dense per-slot KV slabs with static
# shapes instead of paged indirection, because XLA wants static shapes
# and HBM slabs keep the decode matmuls MXU-friendly).  The slabs are
# cheap only while nothing copies them (moved about, they were 59 % of
# the step programs' device time on a v5e): the step programs carry them
# through the layer loop and write the new rows in place
# (``_scan_layers``).  Both run ``apply_block``; what is theirs is which
# rows they write and which slab they attend over.

def init_kv_cache(config: LlamaConfig, slots: int,
                  max_seq: int | None = None) -> dict:
    """Per-slot dense KV slabs: (layers, slots, max_seq, kv_heads, hd).
    A routed model's cache also carries ``routing``, the step programs'
    running counters (``ROUTING_COUNTERS``).

    Whoever jits a step program owns these buffers and DONATES them
    (``llm/engine.py``: ``donate_argnums=(1,)``): every leaf of the
    cache a program returns is then the buffer it was given, rows
    written where they lie, and the dict passed in is dead.  Without
    the donation a call allocates and fills a second whole cache."""
    c = config
    ms = max_seq or c.max_seq
    shape = (c.n_layers, slots, ms, c.n_kv_heads, c.head_dim)
    cache = {
        "k": jnp.zeros(shape, c.dtype),
        "v": jnp.zeros(shape, c.dtype),
        # tokens already written per slot (== next write position)
        "length": jnp.zeros((slots,), jnp.int32),
    }
    if c.num_experts:
        cache["routing"] = jnp.zeros((len(ROUTING_COUNTERS),), jnp.uint32)
    return cache


# What ``prefill_chunk_into_cache`` and ``decode_step`` count of a routed
# model's routing, summed over layers and executions in
# ``cache["routing"]`` (uint32, wraps; a reader takes differences).  They
# count the rows the program computed, padded and idle ones included:
# that is what decides which expert weights a step reads.
ROUTING_COUNTERS = (
    "moe_assignments",    # (row, expert) pairs computed
    "moe_experts_hit",    # experts given at least one row
    "moe_expert_slots",   # experts there were: num_experts a layer
    "moe_load_max",       # rows of each layer's busiest expert, summed
)


def _hoist_experts(layers: dict, c: LlamaConfig):
    """The stacked layers as ``_scan_layers``' scan takes them: ``(the
    leaves it slices layer by layer, the expert matrices it closes over
    whole, the layer indices it scans beside them)`` — see
    ``_routed_mlp`` on why; a dense model's layers are all sliced.  The
    index is also where a layer finds its part of the carried cache."""
    index = jnp.arange(c.n_layers)
    if not c.num_experts:
        return layers, {}, index
    whole = {name: layers[name] for name in ("w_gate", "w_up", "w_down")}
    sliced = {name: leaf for name, leaf in layers.items()
              if name not in whole}
    return sliced, whole, index


def _count_routing(cache: dict, loads) -> dict:
    """``loads``: (layers, num_experts) rows per expert of one
    execution, None for a dense model -> the cache entries to carry."""
    if loads is None:
        return {}
    seen = jnp.stack([jnp.sum(loads), jnp.sum(loads > 0), loads.size,
                      jnp.sum(jnp.max(loads, axis=-1))])
    return {"routing": cache["routing"] + seen.astype(jnp.uint32)}


def prefill_into_cache(params: dict, tokens, cache: dict, slot,
                       length, config: LlamaConfig, *, mesh=None):
    """Run prefill on one padded prompt (1, s) and write its K/V into
    ``slot``; returns (last-token logits (vocab,), new cache).

    ``slot`` and ``length`` may be traced (one compile per prompt
    bucket, none per slot); logits are computed for the last real token
    only — the padded tail writes garbage K/V that decode masks (and
    later overwrites)."""
    last_pos = jnp.maximum(length - 1, 0)
    # Prompt buckets start at 16 tokens: below the flash kernel's tile
    # the blockwise path is named, as the dispatcher demands on a TPU.
    qkv_shape = (1, tokens.shape[1], config.n_heads, config.head_dim)
    logits, ks, vs = forward(
        params, tokens, config, mesh=mesh, return_kv=True,
        logits_at=last_pos,
        attn_impl="auto" if kernel_fits(qkv_shape, qkv_shape)
        else "blockwise")
    cache = dict(cache)
    slot = jnp.asarray(slot, jnp.int32)
    cache["k"] = lax.dynamic_update_slice(
        cache["k"], ks, (0, slot, 0, 0, 0))
    cache["v"] = lax.dynamic_update_slice(
        cache["v"], vs, (0, slot, 0, 0, 0))
    cache["length"] = cache["length"].at[slot].set(length)
    return logits[0], cache


def _attend_slab(xq, ck, cv, pos, c: LlamaConfig):
    """Grouped-query attention of rows ``xq`` (rows, heads, hd), row
    ``r`` over cached positions 0..``pos[r]``: against ONE slab
    (max_seq, kv_heads, hd) that the rows share (a chunk's slot), or a
    slab a row (rows, max_seq, kv_heads, hd) (a decode step's slots).
    bf16 inputs with fp32 accumulation keep the products at full MXU
    rate without an fp32 copy of the slab (see ops/attention)."""
    slab = "rtkd" if ck.ndim == 4 else "tkd"
    q = xq.reshape(xq.shape[0], c.n_kv_heads, c.n_heads // c.n_kv_heads,
                   c.head_dim)
    scores = jnp.einsum(f"rkgd,{slab}->rkgt", q, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(c.head_dim))
    valid = jnp.arange(ck.shape[-3])[None, :] <= pos[:, None]  # (rows, ms)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(f"rkgt,{slab}->rkgd", probs.astype(ck.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(xq.shape).astype(xq.dtype)


def _scan_layers(params: dict, x, cache: dict, c: LlamaConfig, positions,
                 write_attend):
    """A step program's layers over rows ``x`` (rows, dim): one
    ``lax.scan`` of ``apply_block`` whose carry is the rows and the
    whole cache.  Returns (x, new k, new v, loads).

    ``write_attend(ks, vs, i, xq, xk, xv) -> (out, (ks, vs))`` is the
    block's attention over the carried slabs, and has one order: the
    cache travels as the loop's CARRY, which the compiler aliases to the
    donated input, so layer ``i``'s new rows are written where they lie
    (a row whose position is max_seq is dropped by the scatter), and
    only THEN is the slab sliced out of the carried array to feed
    ``_attend_slab``.  As a scanned input and output of the loop the
    slabs are copied about three times a call; attending over the old
    slab with the new rows beside it compiles to more temporaries and
    reorders the float32 sums."""
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta,
                                jnp.float32)
    layers, experts, index = _hoist_experts(params["layers"], c)

    def block(carry, scanned):
        x, ks, vs = carry                        # ks/vs: the whole cache
        layer, i = scanned
        x, (ks, vs), load = apply_block(
            {**layer, **experts}, x, c, cos, sin, positions,
            functools.partial(write_attend, ks, vs, i), _unconstrained, i)
        return (x, ks, vs), load

    (x, ks, vs), loads = lax.scan(
        block, (x, cache["k"], cache["v"]), (layers, index))
    return x, ks, vs, loads


def prefill_chunk_into_cache(params: dict, tokens, cache: dict, slot,
                             start, chunk_len, config: LlamaConfig):
    """Ingest ONE fixed-size chunk of a prompt into ``slot``.

    tokens: (chunk,) int32 — ``chunk_len`` real tokens, zero-padded to
    the engine's fixed chunk width.  ``slot``, ``start`` (absolute
    offset of the chunk in the slab) and ``chunk_len`` are all traced
    scalars, so a single compiled variant covers every chunk of every
    prompt — the chunked-prefill replacement for the O(log max_seq)
    bucketed `prefill_into_cache` variants.

    Chunk queries attend against the slot's FULL slab (earlier chunks'
    K/V plus this chunk's own, causally masked), mirroring
    `decode_step`'s masked-slab attention so the dense-slab static-shape
    discipline holds.  Pad positions write nothing: their scatter
    indices are pushed out of bounds and dropped, and the returned
    logits are taken at the chunk's last REAL token.

    Returns (logits (vocab,) fp32, new cache with slot length set to
    ``start + chunk_len``).
    """
    c = config
    chunk = tokens.shape[0]
    slab = cache["k"].shape[2:]                  # (max_seq, kvh, hd)
    max_seq = slab[0]
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    offs = jnp.arange(chunk, dtype=jnp.int32)
    pos = start + offs                           # (chunk,) absolute
    # Pad tokens' writes land at max_seq → dropped by the scatter; rope
    # positions are clamped only to keep the gather in range (their
    # values never reach the slab or the masked attention).
    write_pos = jnp.where(offs < chunk_len, pos, jnp.int32(max_seq))
    rope_pos = jnp.minimum(pos, jnp.int32(c.max_seq - 1))

    def write_chunk(ks, vs, i, xq, xk, xv):
        """The chunk's real rows into (layer i, slot); attend over that
        slot's slab, causally by absolute position."""
        ks = ks.at[i, slot, write_pos].set(xk.astype(ks.dtype))
        vs = vs.at[i, slot, write_pos].set(xv.astype(vs.dtype))
        ck = lax.dynamic_slice(ks, (i, slot, 0, 0, 0), (1, 1) + slab)[0, 0]
        cv = lax.dynamic_slice(vs, (i, slot, 0, 0, 0), (1, 1) + slab)[0, 0]
        return _attend_slab(xq, ck, cv, pos, c), (ks, vs)

    x = params["embed"][tokens].astype(c.dtype)  # (chunk, dim)
    x, new_k, new_v, loads = _scan_layers(params, x, cache, c, rope_pos,
                                          write_chunk)
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    x_last = jnp.take(x, jnp.maximum(chunk_len - 1, 0), axis=0)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x_last @ head.astype(c.dtype)).astype(jnp.float32)
    cache = {**_count_routing(cache, loads), "k": new_k, "v": new_v,
             "length": cache["length"].at[slot].set(start + chunk_len)}
    return logits, cache


def decode_step(params: dict, last_tokens, cache: dict,
                config: LlamaConfig, active):
    """One token for every slot, attending against the KV cache.

    last_tokens: (slots,) int32 — the most recent token per slot.
    ``active`` ((slots,) bool): slots marked False neither write K/V
    nor advance their length — an idle slot can hold a RESIDENT
    session's slab, which must stay bit-exact while the slot sits out
    decode steps.
    Returns (logits (slots, vocab) fp32, new cache with +1 lengths).
    """
    c = config
    max_seq = cache["k"].shape[2]
    pos = cache["length"]                       # (slots,) write position
    # Inactive slots' scatter writes are pushed out of bounds (and
    # dropped), as a full slot's are; their lengths hold still below.
    write_pos = jnp.where(active, pos, jnp.int32(max_seq))

    slots = jnp.arange(last_tokens.shape[0])

    def write_one(ks, vs, i, xq, xk, xv):
        """One row a slot into layer i; attend over the layer's slabs,
        each slot up to its own position."""
        ks = ks.at[i, slots, write_pos].set(xk.astype(ks.dtype))
        vs = vs.at[i, slots, write_pos].set(xv.astype(vs.dtype))
        ck = lax.dynamic_index_in_dim(ks, i, axis=0,
                                      keepdims=False)  # (slots, ms, kvh, hd)
        cv = lax.dynamic_index_in_dim(vs, i, axis=0, keepdims=False)
        return _attend_slab(xq, ck, cv, pos, c), (ks, vs)

    x = params["embed"][last_tokens].astype(c.dtype)   # (slots, dim)
    x, new_k, new_v, loads = _scan_layers(params, x, cache, c, pos,
                                          write_one)
    x = rmsnorm(x, params["norm_f"], c.norm_eps)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
    # Clamped so a full slot never indexes past its slab.
    new_len = jnp.where(active,
                        jnp.minimum(pos + 1, jnp.int32(max_seq)), pos)
    cache = {**_count_routing(cache, loads), "k": new_k, "v": new_v,
             "length": new_len}
    return logits, cache


# ---------------------------------------------------------------- generate

def greedy_generate(params: dict, config: LlamaConfig, prompt,
                    max_new_tokens: int = 32):
    """Minimal greedy decoding (no KV cache — correctness utility; the
    serving engine owns the fast path)."""
    tokens = jnp.asarray(prompt)[None] if jnp.ndim(prompt) == 1 else prompt

    @jax.jit
    def next_token(toks):
        logits = forward(params, toks, config)
        return jnp.argmax(logits[:, -1], axis=-1)

    for _ in range(max_new_tokens):
        nxt = next_token(tokens)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return tokens
