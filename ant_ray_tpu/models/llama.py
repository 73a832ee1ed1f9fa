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
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ant_ray_tpu.ops import delta_rule, ssd
from ant_ray_tpu.ops.attention import attention
from ant_ray_tpu.ops.layernorm import layernorm
from ant_ray_tpu.ops.pallas import (decode_attention, gather_sum,
                                    grouped_matmul)
from ant_ray_tpu.ops.rmsnorm import rmsnorm
from ant_ray_tpu.ops.rope import (
    YarnScaling,
    apply_rope,
    rope_frequencies,
    yarn_mscale,
)
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
    # RMSNorm of q and k before RoPE, in one of two forms (``_qk_proj``):
    # True, over the WHOLE projection, all heads together, a weight as
    # wide as the projection (OLMoE); "head", over each head's own
    # ``head_dim`` values, ONE weight of that width shared by the heads
    # (Qwen3's family).
    qk_norm: Any = False
    # The router as DeepSeek-V3's family publishes it: ``scoring_func``
    # "sigmoid" scores every expert on its own instead of a softmax over
    # all, and the (normalised) gates are multiplied by
    # ``routed_scaling_factor``.
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # The router's width where this program holds a SHARE of a layer's
    # experts (0 = it holds all): the router scores ``router_width``,
    # the ``num_experts`` held are ``first_expert`` and the ones after
    # it, and what the others would add is left out (the other ranks of
    # an expert-parallel deployment hold them).
    router_width: int = 0
    first_expert: int = 0
    # Experts every token goes through, beside the routed ones: one
    # SwiGLU of width ``n_shared_experts * mlp_dim``.
    n_shared_experts: int = 0
    # ``first_k_dense_replace``: the first layers of a routed model that
    # have a dense SwiGLU of width ``dense_mlp_dim`` instead; they are
    # counted in ``n_layers`` and are a stack of their own in the tree.
    n_dense_layers: int = 0
    dense_mlp_dim: int = 0
    # Latent attention (MLA; 0 = grouped-query): q and k/v come through
    # low-rank projections with an RMSNorm inside, only
    # ``qk_rope_head_dim`` of a head's ``qk_nope_head_dim +
    # qk_rope_head_dim`` are rotated, one rotary key serves all heads,
    # and the cache holds ``kv_lora_rank + qk_rope_head_dim`` values a
    # position instead of keys and values per head (``n_kv_heads`` is
    # not read).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (latent attention only: its ``mscale_all_dim`` temperature
    # is applied where the latent scores are made).
    rope_scaling: YarnScaling | None = None
    # A head's width where the config STATES it (0 = dim // n_heads).
    head_width: int = 0
    # Unlike layers in one model: ``layer_kinds`` names, for each place
    # of the layer pattern's period, the KIND of the layer there; () =
    # every layer is "full".  The period repeats over ``n_layers``.
    # "full": softmax attention over the whole context.  "window": over
    # a sliding window — query ``t`` sees key ``s`` iff 0 <= t - s <
    # ``window``.  "linear": the gated delta rule (``_linear_inputs``,
    # ``ops/delta_rule.py``), which keeps a state of the SEQUENCE and
    # nothing of a position; its leaves are a stack of their own
    # (``LINEAR``).  "ssm": Mamba-2's selective state-space recurrence
    # (``_ssm_inputs``, ``ops/ssd.py``), the other kind that keeps a
    # state of the sequence, of another shape; its leaves are the stack
    # ``SSM``.  "conv": the gated short convolution (LFM2's:
    # ``apply_block``), which keeps of a sequence the last
    # ``conv_L_cache - 1`` inputs of its convolution and NO state
    # matrix; its leaves are the stack ``CONV``.  A model has one
    # RECURRENT kind of the three or none.
    # ONE meaning with or without ``n_dense_layers``: layer ``i`` is of
    # kind ``layer_kinds[i % len(layer_kinds)]`` (``pattern``).  Leading
    # dense layers cut that pattern into two runs — all "conv" the
    # dense one, "full" and "conv" the routed one — and each run is
    # scanned over its OWN shortest period (``stacks``); a published
    # ``layer_types`` of ``n_layers`` entries is a period that repeats
    # once.
    # ``window_pattern`` is the same period written as
    # booleans (window or full), taken at construction only.  With
    # ``full_rope`` False the full layers rotate nothing (no positional
    # embedding at all); window layers always do, recurrent layers never.
    window: int = 0
    layer_kinds: tuple = ()
    window_pattern: dataclasses.InitVar[tuple] = ()
    full_rope: bool = True
    # A linear layer: ``linear_heads`` heads whose queries and keys are
    # ``linear_head_dim`` wide and whose values ``linear_value_dim`` (0:
    # as wide as the keys; the state a head is d_k x d_v, float32), a
    # depth-wise causal convolution of ``linear_conv`` taps over q, k
    # and v.  What the config states of the decay decides the FORM:
    # with ``linear_rank`` the decay is a key CHANNEL's own and comes,
    # like the output's sigmoid gate, through a low-rank pair of that
    # width (Solar Open 2's ``kda``); with ``linear_rank`` 0 there are
    # no such pairs — ONE decay a head, a plain ``dim -> heads``
    # projection as the write strength's is, and the output gate a full
    # ``dim -> heads * d_v`` projection under SiLU (Gated DeltaNet as
    # Olmo Hybrid publishes it, ``gdn``).
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_rank: int = 0
    # A state-space layer: ``ssm_heads`` heads of width
    # ``ssm_head_dim``, each with a state ``ssm_head_dim`` x
    # ``ssm_state`` (float32); the write and the read direction
    # (``ssm_state`` wide each) are shared by the heads of a group, of
    # which there is ONE (``ssm_groups``: the published key, no other
    # count is computed); a depth-wise causal convolution of
    # ``ssm_conv`` taps WITH bias over the heads' inputs and the two
    # directions.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    # A gated short-convolution layer (the published key): ``dim -> 3
    # dim`` gives B, C and u; a depth-wise causal convolution of
    # ``conv_L_cache`` taps a channel, no bias and no activation, runs
    # over B * u; C gates what comes out, before ``dim -> dim``.
    conv_L_cache: int = 0
    # Granite's four scalars, each absent at its default: the embedding
    # is multiplied by ``embedding_multiplier``, what a mix and a
    # feed-forward add to the residual by ``residual_multiplier``, the
    # softmax layers' scores by ``attention_multiplier`` in place of
    # head_dim^-1/2 (0: absent), and the logits are divided by
    # ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # The softmax layers' output under an element-wise sigmoid gate, a
    # projection of the block's normed input, before ``wo``.
    attn_gate: bool = False
    # "rms" (RMSNorm) or "layer": a LayerNorm without bias, over the
    # block's input and before the head (``ops/layernorm.py``).
    norm: str = "rms"
    # Attention and feed-forward from the SAME normed input, one
    # residual sum: x + attn(h) + ffn(h), h = norm(x); no second norm.
    parallel_block: bool = False
    # The shared experts' outputs are averaged, not summed.
    shared_experts_average: bool = False
    # Olmo 2's reordered norm: a sequential block whose two norms sit
    # on each sub-layer's OUTPUT, x + norm(mix(x)) and then x +
    # norm(ffn(x)); nothing norms a sub-layer's input.  The leaves keep
    # their names (``ln_attn``: the mix's norm, ``ln_mlp``: the
    # feed-forward's).
    norm_after: bool = False
    # SANDWICH norms: a sequential block with a norm on each sub-layer's
    # input AND on its output, four a layer, each with its own weight:
    # x + norm(mix(norm(x))), then x + norm(ffn(norm(x))).  The output
    # norms' leaves are ``ln_attn_out`` / ``ln_mlp_out``.
    sandwich_norm: bool = False
    # A LOOPED stack (Ouro, arXiv 2510.25741; the published
    # ``total_ut_steps``): a token goes through the ``n_layers`` layers
    # ``loops`` times, every pass with the SAME weights, and the final
    # norm closes every pass — its output is what the next pass starts
    # from and what the head reads behind the last.  Attention in pass
    # ``u``, layer ``l`` sees the keys and values that pass ``u``,
    # layer ``l`` made of the earlier positions: the cache holds a slab
    # layer for every (pass, layer) pair, at ``u * n_layers + l``
    # (``slab_layers``).  1: every model before it.
    loops: int = 1
    # The looped model's exit gate: one ``dim -> 1`` linear with bias
    # under a sigmoid, read on each pass's normed state
    # (``exit_gate`` in the tree).  The step programs count the exit
    # distribution it gives (``EXIT_COUNTERS``) and choose nothing by
    # it: every row runs every pass.
    exit_gate: bool = False
    # Generation by DIFFUSION OVER BLOCKS (SDAR; 0: every model before
    # it, one token a step).  The sequence is cut into blocks of
    # ``block_length`` positions and attention is BLOCK-causal: position
    # ``t`` sees ``s`` iff ``s // block_length <= t // block_length`` —
    # the blocks before its own and ALL of its own.  A step feeds a
    # slot's whole block in flight, ``block_length`` rows behind its
    # stored length, places not yet decided as ``mask_token``; logits at
    # a place predict the token AT that place (no shift).  Of the masked
    # places a step fills those whose draw came with a probability above
    # ``confidence_threshold`` — or, where fewer than ``block_length //
    # denoising_steps`` did, that many of the largest probability — and
    # a block with no mask left takes one more pass, which stores its
    # keys and values (``decode_step`` with ``store``) — alone, or
    # riding the next block's first step (``closing``); the rule itself
    # is the engine's sampler's, ``llm/sampling.py`` ``_sample_block``.
    block_length: int = 0
    mask_token: int = 0
    denoising_steps: int = 0
    confidence_threshold: float = 0.0
    # SEVERAL RESIDUAL STREAMS a token (manifold-constrained
    # hyper-connections, mHC, arXiv 2512.24880, over hyper-connections,
    # arXiv 2409.19606; 1: one residual vector, every model before it).
    # A token's residual state is ``hc_mult`` streams of ``dim`` values,
    # all equal to its embedding at first (``_embed``) and summed before
    # the final norm (``_closed``).  Every sub-layer has three maps of
    # its own, made of the normed state itself (``hc_maps``): it READS a
    # mix of the streams (``H_pre``), its output goes back to each
    # stream under a weight (``H_post``), and the streams are mixed
    # among themselves by a matrix that ``hc_sinkhorn_iters`` passes of
    # Sinkhorn's normalisation (columns, then rows, each sum +
    # ``hc_eps``) make doubly stochastic, from logits clamped to
    # ``hc_res_clamp`` (``_hc_read``, ``_residual``).
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # DeepSeek-V3's ``noaux_tc`` (arXiv 2412.19437, section 2.1.2): a
    # bias an expert (``router_bias`` in the tree) is added to the
    # scores where the k experts are PICKED; the gates are the picked
    # experts' scores as they were.
    router_bias: bool = False

    def __post_init__(self, window_pattern):
        if window_pattern:
            if self.layer_kinds:
                raise ValueError("window_pattern is layer_kinds written "
                                 "as booleans: give one of them")
            object.__setattr__(self, "layer_kinds", tuple(
                "window" if w else "full" for w in window_pattern))
        if self.rope_scaling is not None and not self.kv_lora_rank:
            raise ValueError("rope_scaling is computed by the latent "
                             "attention only")
        if self.kv_lora_rank and not self.q_lora_rank:
            raise ValueError("latent attention without q_lora_rank is "
                             "not computed")
        if self.n_dense_layers and not self.num_experts:
            raise ValueError("n_dense_layers are the leading dense "
                             "layers of a routed model")
        if set(self.layer_kinds) - {"full", "window", *RECURRENT}:
            raise ValueError(f"unknown layer kinds {self.layer_kinds!r}")
        if len(set(self.layer_kinds) & set(RECURRENT)) > 1:
            raise ValueError(
                "linear (delta-rule) layers, ssm (state-space) layers "
                "and conv (gated short-convolution) layers in ONE model "
                "are not computed: the cache keeps one kind of recurrent "
                "state")
        if bool(self.window) != any(self.period):
            raise ValueError("window and window_pattern go together")
        if self.layer_kinds and (
                self.kv_lora_rank or self.n_layers % len(self.layer_kinds)
                or (self.n_dense_layers and not (
                    set(self.pattern[:self.n_dense_layers]) == {"conv"}
                    and set(self.pattern[self.n_dense_layers:])
                    == {"full", "conv"}))):
            raise ValueError(
                "a window pattern repeats whole over n_layers of "
                "grouped-query layers, none of them a leading dense one "
                "- but where the leading dense ones are all conv (gated "
                "short-convolution) layers and the routed ones full and "
                "conv layers: the two runs are then scanned each over "
                "its own period")
        if self.n_linear and (
                self.window or self.parallel_block
                or "full" not in self.layer_kinds or not (
                    self.linear_heads and self.linear_head_dim
                    and self.linear_conv > 1)
                or (self.linear_rank and self.linear_value_dim not in (
                    0, self.linear_head_dim))):
            raise ValueError(
                "linear layers state their heads, a head's width and "
                "the convolution's taps, stand in a sequential block "
                "beside full layers only, and with a decay a channel "
                "(linear_rank) keep a square state")
        if "ssm" in self.layer_kinds and (
                self.window or self.parallel_block or self.kv_lora_rank
                or "full" not in self.layer_kinds or self.ssm_groups != 1
                or not (self.ssm_heads and self.ssm_head_dim
                        and self.ssm_state and self.ssm_conv > 1)):
            raise ValueError(
                "ssm layers state their heads, a head's width, the "
                "state's width and the convolution's taps, share the "
                "write and read directions among ALL heads (ssm_groups "
                "1), and stand in a sequential block beside full layers "
                "only")
        if "conv" in self.layer_kinds and (
                self.window or self.parallel_block or self.kv_lora_rank
                or self.conv_L_cache < 2):
            raise ValueError(
                "conv layers state their convolution's taps "
                "(conv_L_cache, two at least) and stand in a sequential "
                "block beside full grouped-query layers only (a run of "
                "them alone is a routed model's leading dense layers)")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.norm_after and self.parallel_block:
            raise ValueError("norm_after reorders a sequential block's "
                             "two norms: a parallel block has one, on "
                             "its input")
        if self.parallel_block and self.residual_multiplier != 1.0:
            raise ValueError("residual_multiplier scales what a "
                             "sequential block adds: a parallel block's "
                             "one sum is not scaled")
        if self.sandwich_norm and (self.parallel_block or self.norm_after):
            raise ValueError(
                "sandwich_norm puts a norm on a sequential block's "
                "sub-layer inputs AND outputs: with parallel_block there "
                "is one norm, with norm_after the outputs' alone")
        if self.loops < 1:
            raise ValueError(f"loops {self.loops!r}: a token passes "
                             "through the layers at least once")
        not_looped = {
            "window layers": bool(self.window),
            "a recurrent kind (linear, ssm or conv layers)":
                bool(self.recurrent),
            "latent attention": bool(self.kv_lora_rank),
            "leading dense layers": bool(self.n_dense_layers),
            "routed experts": bool(self.num_experts),
        }
        if self.loops > 1 and any(not_looped.values()):
            raise ValueError(
                "loops run ONE stack of like dense full-attention layers "
                "several times; not computed with " + ", ".join(
                    what for what, found in not_looped.items() if found))
        if self.exit_gate and self.loops == 1:
            raise ValueError("exit_gate reads the state between the "
                             "passes of a looped model: loops is 1")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}: True "
                             "(over the whole projection) or \"head\"")
        if self.hc_mult < 1 or (self.hc_mult > 1 and (
                self.parallel_block or self.loops > 1
                or self.residual_multiplier != 1.0)):
            raise ValueError(
                f"hc_mult {self.hc_mult!r}: several residual streams are "
                "written around the two sub-layers of a sequential block "
                "that runs once; not computed with parallel_block, loops "
                "or a residual_multiplier")
        if self.router_bias and not self.num_experts:
            raise ValueError("router_bias corrects a router's scores: the "
                             "model has no routed experts")
        if self.block_length:
            self._check_blocks()
        elif (self.mask_token or self.denoising_steps
              or self.confidence_threshold):
            raise ValueError(
                "mask_token, denoising_steps and confidence_threshold "
                "belong to generation by diffusion over blocks: "
                "block_length is 0")

    def _check_blocks(self):
        """``__post_init__``'s part for generation by diffusion over
        blocks: its sizes, and what it is not written beside."""
        steps = self.denoising_steps
        if self.block_length < 2 or not 1 <= steps <= self.block_length \
                or self.block_length % steps:
            raise ValueError(
                f"block_length {self.block_length!r} with denoising_steps "
                f"{steps!r}: a block holds at least two places and fills "
                "block_length / denoising_steps of them a step, a whole "
                "number")
        if not 0 <= self.mask_token < self.vocab_size:
            raise ValueError(f"mask_token {self.mask_token!r} is no row of "
                             f"the embedding ({self.vocab_size})")
        if not 0.0 < self.confidence_threshold <= 1.0:
            raise ValueError(
                f"confidence_threshold {self.confidence_threshold!r}: a "
                "probability in (0, 1] (1: the schedule's pace alone)")
        not_blocked = {
            "window layers": bool(self.window),
            "a recurrent kind (linear, ssm or conv layers)":
                bool(self.recurrent),
            "latent attention": bool(self.kv_lora_rank),
            "loops": self.loops > 1,
        }
        if any(not_blocked.values()):
            raise ValueError(
                "block_length (generation by diffusion over blocks) is "
                "written for full grouped-query layers over slabs; not "
                "computed with " + ", ".join(
                    what for what, found in not_blocked.items() if found))

    @property
    def head_dim(self) -> int:
        """Width of a head's query and key."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_width or self.dim // self.n_heads

    @property
    def flat_kv_heads(self) -> bool:
        """Whether a position of the slabs holds its KV heads side by
        side on ONE axis (``kv_slabs``).  A slab (..., positions, heads,
        head_dim) lies with the heads on the sublanes, in tiles of 8:
        up to 8 heads are one tile and 16 are two, and the walk takes a
        block out of the slab where it lies; 30 heads (Olmo Hybrid's
        multi-head layers) are three tiles and three quarters, and the
        TPU compiler then re-lays the WHOLE carried slab, keys heads-
        major and values positions-minor, before every layer's walk —
        6 GiB of temporaries and copies at 8 x 12,288 positions (compiled
        for the described v5e, PERF.md section 6, PR 46).  Side by side,
        every head is a lane tile of its own and positions fill the
        sublanes: nothing is padded and nothing re-laid.  The same
        where whole sublane tiles of heads are each NARROWER than the
        128 lanes and fill whole lane tiles together (8 heads of 64:
        with a heads axis every head's half tile is padded to a whole
        one, and the compiler carried a padded copy of both slabs, 4.5
        GiB of temporaries beside 2.25 GiB of slabs at 96 x 4,096
        positions: compiled for the described v5e, PERF.md section 6,
        PR 65; fewer heads than a sublane tile were not compiled, and
        keep their axis)."""
        return (self.n_kv_heads > 8 and self.n_kv_heads % 8 != 0) or (
            not self.kv_lora_rank and self.n_kv_heads % 8 == 0
            and self.head_dim % 128 != 0
            and self.n_kv_heads * self.head_dim % 128 == 0)

    @property
    def kinds(self) -> tuple:
        """The layer pattern's period: the kind of the layer at each
        place.  ("full",) where all layers are alike."""
        return self.layer_kinds or ("full",)

    @property
    def pattern(self) -> tuple:
        """The kind of every one of the ``n_layers``, in order."""
        return self.kinds * (self.n_layers // len(self.kinds))

    @property
    def period(self) -> tuple:
        """``kinds`` as booleans: whether a place's layer is a window
        layer."""
        return tuple(kind == "window" for kind in self.kinds)

    @property
    def n_linear(self) -> int:
        """Linear layers of the ``n_layers``."""
        return self.n_layers // len(self.kinds) * self.kinds.count("linear")

    @property
    def linear_widths(self) -> tuple:
        """(d_k, d_v) of a linear layer's head: its state's shape."""
        return (self.linear_head_dim,
                self.linear_value_dim or self.linear_head_dim)

    @property
    def recurrent(self) -> str:
        """The model's RECURRENT kind — "linear", "ssm", "conv" — or
        "": the kind of layer that keeps a state a slot
        (``state_slabs``) and nothing of a position."""
        return next((kind for kind in RECURRENT if kind in self.kinds), "")

    @property
    def n_recurrent(self) -> int:
        """Layers of the ``n_layers`` that keep a state a slot."""
        return self.n_layers // len(self.kinds) * self.kinds.count(
            self.recurrent)

    def layer_counts(self) -> tuple:
        """(window layers, full layers) of the ``n_layers``."""
        n_window = self.n_layers // len(self.kinds) * sum(self.period)
        return n_window, self.n_layers - n_window - self.n_recurrent

    def slab_layers(self) -> tuple:
        """(ring layers, slab layers) of the CACHE, the one place that
        says so: a window layer's ring and a full layer's slab for
        every (pass, layer) pair — ``layer_counts`` times ``loops``."""
        n_window, n_full = self.layer_counts()
        return n_window * self.loops, n_full * self.loops

    def place(self, j: int) -> tuple:
        """Where the layer at place ``j`` of the period lies: (its
        stack's name in a ``stacks`` entry's parameters, that stack's
        layers a period, ``j``'s rank among them)."""
        stack = RECURRENT.get(self.kinds[j], "layers")
        alike = [i for i, kind in enumerate(self.kinds)
                 if RECURRENT.get(kind, "layers") == stack]
        return stack, len(alike), alike.index(j)

    @property
    def rope_dim(self) -> int:
        """How much of a head is rotated."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def attn_scale(self) -> float:
        """The softmax scale where it is NOT plain head_dim^-1/2 (the
        grouped-query paths spell that one out themselves): the
        ``attention_multiplier`` a config states, or the latent scores'
        head_dim^-1/2 times the square of YaRN's ``mscale_all_dim``
        temperature."""
        scale = self.attention_multiplier or self.head_dim ** -0.5
        s = self.rope_scaling
        if s is not None and s.mscale_all_dim:
            scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
        return scale

    def stacks(self) -> dict:
        """The model's layers in order, as RUNS of layers with one
        feed-forward: the run's name -> the config that reads it
        (``n_layers`` its own, ``layer_kinds`` its own period).  A
        routed model's leading dense layers are ``dense_layers``;
        everything else is ``layers``.  A run's softmax layers lie in
        the parameter tree under the run's name, its recurrent layers
        in a stack beside it (``run_stacks``)."""
        if not self.n_dense_layers:
            return {"layers": self}
        lead, routed = ({"layer_kinds": _shortest_period(run)}
                        if self.layer_kinds else {} for run in (
                            self.pattern[:self.n_dense_layers],
                            self.pattern[self.n_dense_layers:]))
        rest = dataclasses.replace(
            self, n_layers=self.n_layers - self.n_dense_layers,
            n_dense_layers=0, **routed)
        dense = dataclasses.replace(
            rest, n_layers=self.n_dense_layers, num_experts=0,
            router_width=0, router_bias=False, n_shared_experts=0,
            mlp_dim=self.dense_mlp_dim, **lead)
        return {"dense_layers": dense, "layers": rest}

    def num_params(self) -> int:
        """Every parameter this program holds (of a share, the share)."""
        return sum(math.prod(shape) for shape in jax.tree.leaves(
            param_shapes(self), is_leaf=lambda x: isinstance(x, tuple)))


# The stack of a model's linear layers in the parameter tree, beside
# ``layers`` (its softmax layers): a linear layer's leaves are not a
# softmax layer's, so the two kinds cannot lie in one stacked array.
# ``SSM``: the same for its state-space layers.  ``RECURRENT``: the
# kinds that keep a state a slot -> their stack's name.
LINEAR = "linear_layers"
SSM = "ssm_layers"
CONV = "conv_layers"
RECURRENT = {"linear": LINEAR, "ssm": SSM, "conv": CONV}


def _shortest_period(kinds: tuple) -> tuple:
    """The shortest tuple that ``kinds`` is whole repetitions of."""
    return next(kinds[:n] for n in range(1, len(kinds) + 1)
                if kinds == kinds[:n] * (len(kinds) // n))


def run_stacks(name: str, run: LlamaConfig) -> dict:
    """Where the layers of a run (an entry of ``LlamaConfig.stacks``)
    lie in the parameter tree: the stack's name WITHIN the run, as
    ``LlamaConfig.place`` gives it -> its name in the tree.  The
    softmax layers' stack has the run's name; a recurrent kind's stands
    beside it under the kind's name — ``dense_layers``' under
    ``dense_`` and that name, for the dense feed-forward's leaves are
    not the routed one's."""
    stacks = {"layers": name} if run.n_layers > run.n_recurrent else {}
    if run.recurrent:
        beside = RECURRENT[run.recurrent]
        stacks[beside] = name[:-len("layers")] + beside
    return stacks

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
    # A.X-K1's block at test size (DeepSeek-V3's family): latent
    # attention (4 heads of 16 + 8 / 16, ranks 24 / 16, YaRN 4 x 32), one
    # dense layer, then two whose sigmoid router scores 8 experts, 2 a
    # token, of which this share holds 4 (experts 0-3), beside a shared one
    "axk1-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
        mlp_dim=32, max_seq=512, rope_theta=10000.0, norm_eps=1e-6,
        dtype=jnp.float32, num_experts=4, experts_per_token=2,
        router_scoring="sigmoid", routed_scaling_factor=2.5,
        router_width=8, n_shared_experts=1, n_dense_layers=1,
        dense_mlp_dim=96, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=YarnScaling(
            factor=4.0, original_max_position_embeddings=32,
            mscale=1.0, mscale_all_dim=1.0)),
    # Xing4.0's block at test size: A.X-K1's latent attention and YaRN,
    # two leading dense layers, then two whose sigmoid router picks 2 of
    # 8 experts (all held) by score + correction bias, beside a shared
    # one — and FOUR residual streams a token, a read, a write and a
    # Sinkhorn-projected mix around every sub-layer
    "xing4-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=4,
        mlp_dim=32, max_seq=512, rope_theta=10000.0, norm_eps=1e-6,
        dtype=jnp.float32, num_experts=8, experts_per_token=2,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.0, n_shared_experts=1, n_dense_layers=2,
        dense_mlp_dim=96, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=YarnScaling(
            factor=4.0, original_max_position_embeddings=32,
            mscale=1.0, mscale_all_dim=1.0), hc_mult=4),
    # Command A+'s block at test size (Cohere2-MoE): two periods of three
    # window layers (16 positions, rotated) and a full one (no positional
    # embedding), 8 heads of 16 on a hidden size of 64, ONE LayerNorm a
    # block with attention and feed-forward in parallel under it, a
    # sigmoid router over 8 experts, 2 a token, of which this share
    # holds 4, four shared experts averaged, the embedding tied
    "cmdaplus-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=8, n_kv_heads=2,
        head_width=16, mlp_dim=32, max_seq=512, rope_theta=50000.0,
        norm_eps=1e-5, dtype=jnp.float32, tie_embeddings=True,
        num_experts=4, experts_per_token=2, router_scoring="sigmoid",
        router_width=8, n_shared_experts=4, shared_experts_average=True,
        window=16, window_pattern=(True, True, True, False),
        full_rope=False, norm="layer", parallel_block=True),
    # Solar Open 2's block at test size: two periods of a gated softmax
    # layer without positional embedding (4 query / 2 KV heads of 16)
    # and three gated delta-rule layers (4 heads of 16 x 16, conv of 4
    # taps, low-rank width 8), a sigmoid router over 16 experts, 2 a
    # token, of which this share holds 8, beside one shared expert
    "solar2-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq=512, norm_eps=1e-5, dtype=jnp.float32,
        num_experts=8, experts_per_token=2, router_scoring="sigmoid",
        router_width=16, n_shared_experts=1, full_rope=False,
        layer_kinds=("full", "linear", "linear", "linear"),
        linear_heads=4, linear_head_dim=16, linear_rank=8, attn_gate=True),
    # Granite 4.0-H's block at test size: two periods of two Mamba-2
    # layers (4 heads of 16 with a state of 16, conv of 4 taps with
    # bias), a softmax layer without positional embedding (4 query / 2
    # KV heads of 16, scale 1/16 stated) and a third Mamba-2 layer; a
    # softmax router over 8 experts, 3 a token, of which this share
    # holds 4, beside a shared SwiGLU twice an expert's width; the four
    # multipliers set, the embedding tied
    "granite-h-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq=512, norm_eps=1e-5, dtype=jnp.float32,
        tie_embeddings=True, num_experts=4, experts_per_token=3,
        router_width=8, n_shared_experts=2, full_rope=False,
        layer_kinds=("ssm", "ssm", "full", "ssm"),
        ssm_heads=4, ssm_head_dim=16, ssm_state=16,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 16, logits_scaling=16.0),
    # Olmo Hybrid's block at test size: two periods of three gated
    # delta-rule layers with ONE decay a head (4 heads, a state of 8 x
    # 16: keys and values of unlike widths, conv of 4 taps, no low-rank
    # pairs, the output gate a full projection under SiLU) and a
    # multi-head softmax layer without positional embedding (4 heads of
    # 16, RMSNorm over the whole q and k), the norms on each
    # sub-layer's output, dense
    "olmo-hybrid-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=4,
        mlp_dim=96, max_seq=512, norm_eps=1e-6, dtype=jnp.float32,
        qk_norm=True, full_rope=False, norm_after=True,
        layer_kinds=("linear", "linear", "linear", "full"),
        linear_heads=4, linear_head_dim=8, linear_value_dim=16),
    # LFM2-8B-A1B's stack at test size: two leading DENSE layers that
    # are gated short-convolution layers (3 taps, a tail of 2 x 64 a
    # slot and no state matrix), then two periods of a softmax layer (4
    # query / 2 KV heads of 16, an RMSNorm a head on q and k, rotated)
    # and three more conv layers; a sigmoid router that picks 2 of 8
    # experts by score + bias and divides their scores by their sum, no
    # shared expert; the embedding tied
    "lfm2-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=10, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq=512, rope_theta=1000000.0, norm_eps=1e-5,
        dtype=jnp.float32, tie_embeddings=True, num_experts=8,
        experts_per_token=2, router_scoring="sigmoid", router_bias=True,
        n_dense_layers=2, dense_mlp_dim=96, qk_norm="head",
        layer_kinds=("conv", "conv") + ("full", "conv", "conv", "conv") * 2,
        conv_L_cache=3),
    # Ouro's stack at test size: three layers that a token passes
    # through three times (nine slab layers in the cache), 4 heads of
    # 16, plain multi-head, rotated, four norms a layer (sandwich), the
    # final norm between the passes, the exit gate, dense
    "ouro-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
        mlp_dim=96, max_seq=512, rope_theta=1000000.0, norm_eps=1e-6,
        dtype=jnp.float32, sandwich_norm=True, loops=3, exit_gate=True),
    # SDAR's block at test size: generation by diffusion over blocks of
    # 4 (four denoising steps: one place a step at the schedule's pace,
    # more where a draw passes 0.9; the mask is token 255), 4 query / 2
    # KV heads of 16 with an RMSNorm a head on q and k, a softmax router
    # over 8 experts, 2 a token, gates renormalised
    "sdar-tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq=512, rope_theta=1000000.0, norm_eps=1e-6,
        dtype=jnp.float32, num_experts=8, experts_per_token=2,
        qk_norm="head", block_length=4, mask_token=255,
        denoising_steps=4, confidence_threshold=0.9),
}


# ---------------------------------------------------------------- params

def _layer_leaves(c: LlamaConfig, stack: str = "layers") -> dict:
    """One stack of like layers: leaf name -> (its shape after the
    leading layers axis, its logical dims after it); ``stack``: the
    model's linear layers (``LINEAR``), its state-space layers
    (``SSM``), its gated short-convolution layers (``CONV``), else its
    softmax layers.  A leaf's last logical dim also says how
    ``init_params`` draws it."""
    hd, e, p, m = c.head_dim, "embed_param", "heads_flat", "mlp"
    if stack == CONV:
        attn = {
            # B, C and u side by side, in that order
            "in_proj": ((c.dim, 3 * c.dim), (e, p)),
            "conv_w": ((c.conv_L_cache, c.dim), (None, "taps")),
            "wo": ((c.dim, c.dim), (p, e)),
        }
    elif stack == SSM:
        heads, inner, channels = c.ssm_heads, *ssm_widths(c)
        attn = {
            # the gate z, the convolution's channels, a step a head
            "in_proj": ((c.dim, inner + channels + heads), (e, p)),
            "conv_w": ((c.ssm_conv, channels), (None, "taps")),
            "conv_b": ((channels,), ("bias",)),
            "dt_bias": ((heads,), ("decay_bias",)),
            "a_log": ((heads,), ("decay_rate",)),
            "d_skip": ((heads,), ("norm",)),
            # one RMS over all the heads' channels, behind the gate
            "ssm_norm": ((inner,), ("norm",)),
            "wo": ((inner, c.dim), (p, e)),
        }
    elif stack == LINEAR:
        heads, rank = c.linear_heads, c.linear_rank
        d_k, d_v = c.linear_widths
        width, v_width = heads * d_k, heads * d_v
        attn = {
            "wq": ((c.dim, width), (e, p)),
            "wk": ((c.dim, width), (e, p)),
            "wv": ((c.dim, v_width), (e, p)),
            # q, k and v side by side, as the cache keeps their tails
            "conv_w": ((c.linear_conv, 2 * width + v_width),
                       (None, "conv")),
            **({
                # the decay: a low-rank pair, a rate a head, a bias a
                # channel
                "w_fa": ((c.dim, rank), (e, None)),
                "w_fb": ((rank, width), (None, p)),
                "a_log": ((heads,), ("decay_rate",)),
                "dt_bias": ((width,), ("decay_bias",)),
            } if rank else {
                # ONE decay a head: a projection, a rate, a bias
                "w_a": ((c.dim, heads), (e, None)),
                "a_log": ((heads,), ("decay_rate",)),
                "dt_bias": ((heads,), ("decay_bias",)),
            }),
            "w_beta": ((c.dim, heads), (e, None)),
            # the output gate — a low-rank pair or one full projection —
            # and the norm a head before it
            **({"w_ga": ((c.dim, rank), (e, None)),
                "w_gb": ((rank, width), (None, p))} if rank else
               {"w_g": ((c.dim, v_width), (e, p))}),
            "o_norm": ((d_v,), ("norm",)),
            "wo": ((v_width, c.dim), (p, e)),
        }
    elif c.kv_lora_rank:
        attn = {
            "w_qa": ((c.dim, c.q_lora_rank), (e, None)),
            "q_a_norm": ((c.q_lora_rank,), ("norm",)),
            "w_qb": ((c.q_lora_rank, c.n_heads * hd), (None, p)),
            "w_kva": ((c.dim, c.kv_lora_rank + c.qk_rope_head_dim),
                      (e, None)),
            "kv_a_norm": ((c.kv_lora_rank,), ("norm",)),
            "w_kvb": ((c.kv_lora_rank, c.n_heads * (
                c.qk_nope_head_dim + c.v_head_dim)), (None, p)),
            "wo": ((c.n_heads * c.v_head_dim, c.dim), (p, e)),
        }
    else:
        attn = {
            "wq": ((c.dim, c.n_heads * hd), (e, p)),
            "wk": ((c.dim, c.n_kv_heads * hd), (e, p)),
            "wv": ((c.dim, c.n_kv_heads * hd), (e, p)),
            "wo": ((c.n_heads * hd, c.dim), (p, e)),
            **({"w_attn_gate": ((c.dim, c.n_heads * hd), (e, p))}
               if c.attn_gate else {}),
        }
    if c.num_experts:
        n = c.num_experts
        mlp = {
            "router": ((c.dim, c.router_width or n), (None, "experts")),
            **({"router_bias": ((c.router_width or n,), ("router_bias",))}
               if c.router_bias else {}),
            "w_gate": ((n, c.dim, c.mlp_dim), ("experts", e, m)),
            "w_up": ((n, c.dim, c.mlp_dim), ("experts", e, m)),
            "w_down": ((n, c.mlp_dim, c.dim), ("experts", m, e)),
        }
        if c.n_shared_experts:
            width = c.n_shared_experts * c.mlp_dim
            mlp.update({
                "shared_gate": ((c.dim, width), (e, m)),
                "shared_up": ((c.dim, width), (e, m)),
                "shared_down": ((width, c.dim), (m, e)),
            })
    else:
        mlp = {
            "w_gate": ((c.dim, c.mlp_dim), (e, m)),
            "w_up": ((c.dim, c.mlp_dim), (e, m)),
            "w_down": ((c.mlp_dim, c.dim), (m, e)),
        }
    return {
        "ln_attn": ((c.dim,), ("norm",)),
        **attn,
        **({} if c.parallel_block else {"ln_mlp": ((c.dim,), ("norm",))}),
        **mlp,
        **({"ln_attn_out": ((c.dim,), ("norm",)),
            "ln_mlp_out": ((c.dim,), ("norm",))} if c.sandwich_norm else {}),
        **({"q_norm": ((hd if c.qk_norm == "head" else c.n_heads * hd,),
                       ("norm",)),
            "k_norm": ((hd if c.qk_norm == "head" else c.n_kv_heads * hd,),
                       ("norm",))}
           if c.qk_norm and stack == "layers" else {}),
        **({f"hc_{sub}_{name}": both for sub in ("attn", "mlp")
            for name, both in _hc_leaves(c).items()}
           if c.hc_mult > 1 else {}),
    }


def _hc_leaves(c: LlamaConfig) -> dict:
    """ONE sub-layer's leaves of the residual streams' maps
    (``hc_maps``): ``phi`` from the normed state, all streams side by
    side, to the maps' ``n`` + ``n`` + ``n * n`` logits, their bias
    ``b`` and the three scalars ``alpha`` (read, write, mix) that scale
    what depends on the input."""
    n = c.hc_mult
    return {"phi": ((n * c.dim, 2 * n + n * n), ("embed_param", "hc_maps")),
            "b": ((2 * n + n * n,), ("hc_bias",)),
            "alpha": ((3,), ("norm",))}


def ssm_widths(c: LlamaConfig) -> tuple:
    """(the heads' channels together, the convolution's channels: those
    and the write and the read direction) of a state-space layer."""
    inner = c.ssm_heads * c.ssm_head_dim
    return inner, inner + 2 * c.ssm_groups * c.ssm_state


def _param_tree(config: LlamaConfig, pick) -> dict:
    """The parameter tree with ``pick(layers, shape, dims)`` at every
    leaf (``layers`` None outside the stacks).  A routed model's leading
    dense layers are a stack of their own, ``dense_layers``, beside
    ``layers``, which then holds the routed ones only; a run's
    recurrent layers are a stack beside its softmax layers'
    (``run_stacks``), and a run that has none of a sort has no stack of
    it."""
    c = config
    stacks = {}
    for run_name, run in c.stacks().items():
        for stack, name in run_stacks(run_name, run).items():
            stacks[name] = (
                run.n_recurrent if stack in RECURRENT.values()
                else run.n_layers - run.n_recurrent,
                _layer_leaves(run, stack))
    return {
        "embed": pick(None, (c.vocab_size, c.dim), ("vocab", "embed_param")),
        **{name: {leaf: pick(n, *both) for leaf, both in leaves.items()}
           for name, (n, leaves) in stacks.items()},
        "norm_f": pick(None, (c.dim,), ("norm",)),
        **({"exit_gate": {"w": pick(None, (c.dim, 1), ("embed_param", None)),
                          "b": pick(None, (1,), ("bias",))}}
           if c.exit_gate else {}),
        **({} if c.tie_embeddings else {"lm_head": pick(
            None, (c.dim, c.vocab_size), ("embed_param", "vocab"))}),
    }


def param_shapes(config: LlamaConfig) -> dict:
    return _param_tree(config, lambda n, shape, _dims: (
        shape if n is None else (n, *shape)))


def param_logical_dims(config: LlamaConfig) -> dict:
    """Logical dim names per param (see parallel/sharding.py rules)."""
    return _param_tree(config, lambda n, _shape, dims: (
        dims if n is None else (None, *dims)))


# extra rules: flattened (heads*head_dim) dims shard over tp; a
# recurrent layer's small leaves, named for how they are drawn, are
# replicated
LLAMA_RULES_EXTRA = {"heads_flat": "tp", "conv": None, "taps": None,
                     "bias": None, "decay_rate": None, "decay_bias": None,
                     "hc_maps": None, "hc_bias": None, "router_bias": None}


def llama_rules() -> dict:
    from ant_ray_tpu.parallel.sharding import DEFAULT_LLAMA_RULES  # noqa: PLC0415

    rules = dict(DEFAULT_LLAMA_RULES)
    rules.update(LLAMA_RULES_EXTRA)
    return rules


# How ``init_params`` draws the leaves that only several residual
# streams (``hc_mult``) and a bias-corrected router have: logical name
# -> the standard deviation for a shape.  ``phi`` at (streams * dim)^-1/2
# makes the input's part of every map's logit of order 1 under ``alpha``
# 1 (the normed state has unit mean square), the maps' bias is a unit
# normal, and the router's bias a tenth — of the size of the gaps between
# a token's sigmoid scores, so that it changes picks without deciding
# them alone.  A published checkpoint starts near ``alpha`` 0.01 and a
# mix close to the identity; random weights drawn so would make the maps
# constants, and no comparison with a reference would see a fault in
# them.
HC_DRAWS = {"hc_maps": lambda shape: shape[-2] ** -0.5,
            "hc_bias": lambda shape: 1.0,
            "router_bias": lambda shape: 0.1}


def init_params(config: LlamaConfig, key) -> dict:
    def is_leaf(x):
        return isinstance(x, tuple)

    flat, treedef = jax.tree.flatten(param_shapes(config), is_leaf=is_leaf)
    dims = jax.tree.leaves(param_logical_dims(config), is_leaf=is_leaf)
    keys = jax.random.split(key, len(flat))

    def _init(shape, logical, k):
        if logical[-1] == "norm":
            return jnp.ones(shape, config.dtype)
        if logical[-1] == "bias":
            return jnp.zeros(shape, config.dtype)
        if logical[-1] == "decay_rate":
            # a_log: a head forgets at a rate drawn from 1 to 16 ...
            drawn = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1, 16))
        elif logical[-1] == "decay_bias":
            # ... times a channel's step of 0.001 to 0.1, log-uniform
            # (dt_bias is its inverse softplus): the family's
            # convention, half-lives from under one token to hundreds
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            drawn = dt + jnp.log(-jnp.expm1(-dt))
        elif logical[-1] == "taps":
            # a state-space layer's convolution, as that family draws
            # it: uniform within +-taps^-1/2.  What it writes and reads
            # the state along is then of the size of its input, and the
            # state is a large part of the layer's output; taps at 0.02
            # leave the state a thousandth of the skip beside it
            # (a linear layer sets q and k to unit length instead)
            drawn = jax.random.uniform(
                k, shape, jnp.float32, -1.0, 1.0) * shape[-2] ** -0.5
        elif logical[-1] in HC_DRAWS:
            # the residual streams' maps and the router's correction
            # bias, at sizes at which they MATTER (``HC_DRAWS``)
            drawn = jax.random.normal(k, shape, jnp.float32) * HC_DRAWS[
                logical[-1]](shape)
        else:
            # every matrix, a linear layer's taps among them
            drawn = jax.random.normal(k, shape, jnp.float32) * 0.02
        return drawn.astype(config.dtype)

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
                attend, constrain_act, index=None, kind: str = "full",
                tile: int = 0, live=None):
    """One transformer block on ``x`` (..., dim), the only place its
    equations are written: training hands it (batch, seq, dim), a
    prefill chunk and a decode step their rows, (chunk, dim) and
    (slots, dim).  The three differ in how they attend:
    ``attend(xq, xk, xv) -> (out, state)`` takes rotated queries and
    keys and the values, heads split, and returns the attention output,
    shaped as the queries, beside what the caller keeps of the layer —
    nothing, its (xk, xv), or the KV cache with its rows written
    (``_scan_layers``).  With latent attention the block hands it a
    position's latent and its one rotary key in the keys' and values'
    places, and the layer's up-projection as a fourth argument
    (``_latent_qkv``).  ``positions``: int32 of ``x``'s leading shape,
    None for arange over the sequence.  ``index``: the layer's number
    where ``layer`` holds the whole stack's expert matrices, ``tile``
    the grouped kernel's row tile there and ``live`` which of the rows
    anybody reads (``_routed_mlp``); only a STEP
    program passes it, and the products it reshapes to heads and its
    MLP's then spell their rounding out (``_proj``, ``_swiglu``).
    ``kind``: the layer's, of ``LlamaConfig.kinds``.  A "window"
    layer's ``attend`` masks accordingly; here the kind decides whether
    the heads are rotated (a full layer of a ``full_rope=False`` model
    rotates nothing).  A "linear" layer hands ``attend(u, g, beta,
    conv_w)`` what ``_linear_inputs`` makes of the normed input — q, k
    and v BEFORE their convolution, whose last inputs the caller may
    hold, the log-decays and the write strengths — and gets back the
    heads' outputs (..., heads, d_v) float32, which it norms a head,
    gates and projects.  An "ssm" layer hands ``attend(u, dt,
    weights)`` what ``_ssm_inputs`` makes of it — the convolution's
    channels BEFORE it and the steps, beside the layer's small leaves —
    and gets back the heads' outputs (..., heads, P) float32, which it
    gates FIRST and then norms over all channels at once.  A "conv"
    layer hands ``attend(bu, conv_w)`` the gated input ``B * u`` of its
    convolution (..., dim), rounded to the activations' dtype as the
    cache keeps its tail, and gets back the convolution's sums (...,
    dim) float32, which it gates with ``C``.  With
    ``parallel_block`` attention and feed-forward read the same normed
    input and join in one residual sum; otherwise what either adds to
    the residual is multiplied by ``residual_multiplier`` where the
    config states one.  With ``norm_after`` the block's two norms sit
    on the sub-layers' OUTPUTS — x + norm(mix(x)), x + norm(ffn(x)) —
    and a sub-layer reads the residual as it is; with ``sandwich_norm``
    on its input and its output both — x + norm(mix(norm(x))) — the
    outputs' under leaves of their own.  Of a model with several
    residual streams (``hc_mult``) ``x`` is (..., hc_mult, dim): either
    sub-layer reads a mix of the streams and its output goes back to all
    of them (``_hc_read``, ``_residual``); everything between is as
    written above.  Returns ``(x, state, load)``, ``load`` as ``_mlp``
    gives it."""
    step = index is not None
    x, streams = _hc_read(layer, x, c, "hc_attn")
    lead = x.shape[:-1]
    h = x if c.norm_after else _norm(x, layer["ln_attn"], c)
    if kind == "ssm":
        with jax.named_scope("attn_ssm"):
            z, u, dt = _ssm_inputs(layer, h, c)
            attn, state = attend(u, dt, {name: layer[name] for name in (
                "conv_w", "conv_b", "a_log", "d_skip")})
            attn = rmsnorm(attn.reshape(*lead, -1) * jax.nn.silu(z),
                           layer["ssm_norm"].astype(jnp.float32),
                           c.norm_eps).astype(x.dtype)
    elif kind == "conv":
        with jax.named_scope("short_conv"):
            b, gate, u = jnp.split(jnp.dot(
                h, layer["in_proj"], preferred_element_type=jnp.float32),
                3, axis=-1)
            attn, state = attend((b * u).astype(h.dtype), layer["conv_w"])
            attn = (gate * attn).astype(x.dtype)
    elif kind == "linear":
        with jax.named_scope("attn_linear"):
            attn, state = attend(*_linear_inputs(layer, h, c),
                                 layer["conv_w"])
            if c.linear_rank:
                gate = jax.nn.sigmoid(jnp.dot(
                    h @ layer["w_ga"], layer["w_gb"],
                    preferred_element_type=jnp.float32))
            else:
                gate = jax.nn.silu(jnp.dot(
                    h, layer["w_g"], preferred_element_type=jnp.float32))
            attn = rmsnorm(attn, layer["o_norm"].astype(jnp.float32),
                           c.norm_eps).reshape(*lead, -1) * gate
            attn = attn.astype(x.dtype)
    elif c.kv_lora_rank:
        with jax.named_scope("mla"):
            xq, c_kv, k_rope = _latent_qkv(layer, h, c, cos, sin, positions,
                                           step)
            attn, state = attend(xq, c_kv, k_rope, layer["w_kvb"])
    else:
        xq, xk = _qk_proj(layer, h, c, step)
        xq = xq.reshape(*lead, c.n_heads, c.head_dim)
        xk = xk.reshape(*lead, c.n_kv_heads, c.head_dim)
        xv = _proj(h, layer["wv"], step).reshape(
            *lead, c.n_kv_heads, c.head_dim)
        if kind == "window" or c.full_rope:
            xq = apply_rope(xq, cos, sin, positions)
            xk = apply_rope(xk, cos, sin, positions)
        xq = constrain_act(xq, ("batch", "seq", "heads", "head_dim"))
        xk = constrain_act(xk, ("batch", "seq", "kv_heads", "head_dim"))
        with jax.named_scope("attn_" + kind):
            attn, state = attend(xq, xk, xv)
    attn = attn.reshape(*lead, -1)               # heads * value width
    if c.attn_gate and kind not in RECURRENT:
        attn = (attn * jax.nn.sigmoid(jnp.dot(
            h, layer["w_attn_gate"],
            preferred_element_type=jnp.float32))).astype(x.dtype)
    attn = (attn @ layer["wo"]).astype(x.dtype)
    if c.norm_after:
        attn = _norm(attn, layer["ln_attn"], c)
    if c.sandwich_norm:
        attn = _norm(attn, layer["ln_attn_out"], c)
    if c.parallel_block:
        out, load = _mlp(layer, h, c, index, tile, live)
        x = x + attn + out.astype(x.dtype)
        return constrain_act(x, ("batch", "seq", "embed")), state, load
    x = _residual(x, attn, c, streams)
    x = constrain_act(x, ("batch", "seq", "embed"))

    x, streams = _hc_read(layer, x, c, "hc_mlp")
    h = x if c.norm_after else _norm(x, layer["ln_mlp"], c)
    out, load = _mlp(layer, h, c, index, tile, live)
    out = out.astype(x.dtype)
    if c.norm_after:
        out = _norm(out, layer["ln_mlp"], c)
    if c.sandwich_norm:
        out = _norm(out, layer["ln_mlp_out"], c)
    x = _residual(x, out, c, streams)
    x = constrain_act(x, ("batch", "seq", "embed"))
    return x, state, load


def _residual(x, out, c: LlamaConfig, streams=None):
    """``x`` + what a mix or a feed-forward adds to it, under the
    config's ``residual_multiplier`` (the product in float32, rounded
    once).  With ``streams`` (``_hc_read``'s: the residual streams the
    sub-layer read ``x`` from, and its write and mix maps) what comes
    back is the streams: stream i is ``sum_j H_res[i, j] X_j + H_post[i]
    out``, in float32, the n + 1 terms added in their order, rounded
    once."""
    if streams is not None:
        with jax.named_scope("hc"):
            held, h_post, h_res = streams
            wide = held.astype(jnp.float32)
            mixed = h_res[..., :, 0, None] * wide[..., 0, None, :]
            for j in range(1, c.hc_mult):
                mixed = mixed + h_res[..., :, j, None] * wide[..., j, None, :]
            mixed = mixed + h_post[..., :, None] * out.astype(
                jnp.float32)[..., None, :]
            return mixed.astype(held.dtype)
    if c.residual_multiplier == 1.0:
        return x + out
    return x + (out.astype(jnp.float32)
                * c.residual_multiplier).astype(x.dtype)


def hc_maps(phi, b, alpha, x, c: LlamaConfig):
    """A sub-layer's three maps of the residual streams ``x`` (..., n,
    dim), n = ``hc_mult``, all float32: ``H_pre`` (..., n), how it reads
    them; ``H_post`` (..., n), how its output goes back to each; ``H_res``
    (..., n, n), how they are mixed among themselves.  With ``x_hat`` the
    streams side by side over their root mean square (no learned scale:
    it folds into ``phi``) and ``[p | q | r] = x_hat phi``:

        H_pre  = sigmoid(alpha_0 p + b_p)
        H_post = 2 sigmoid(alpha_1 q + b_q)
        H_res  = Sinkhorn(exp(clamp(alpha_2 r + b_r)))

    Sinkhorn: ``hc_sinkhorn_iters`` times, every column over its sum +
    ``hc_eps``, then every row over its sum + ``hc_eps`` — a positive
    matrix's way to a doubly stochastic one (mHC's manifold: the mix
    neither grows nor shrinks what the streams carry together).  The
    product with ``phi`` is made of the streams as they are and scaled
    by the root mean square behind it (the same sums), at the HIGHEST
    precision: the TPU's default would round its float32 operands to
    bfloat16."""
    n = c.hc_mult
    wide = x.astype(jnp.float32).reshape(*x.shape[:-2], n * x.shape[-1])
    inv_rms = lax.rsqrt(jnp.mean(wide * wide, axis=-1, keepdims=True)
                        + c.norm_eps)
    b, alpha = b.astype(jnp.float32), alpha.astype(jnp.float32)
    pqr = jnp.dot(wide, phi.astype(jnp.float32),
                  precision=lax.Precision.HIGHEST) * inv_rms
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * pqr[..., 2 * n:] + b[2 * n:],
                         *c.hc_res_clamp)).reshape(*pqr.shape[:-1], n, n)
    for _ in range(c.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + c.hc_eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + c.hc_eps)
    return h_pre, h_post, m


def _hc_read(layer: dict, x, c: LlamaConfig, sub: str):
    """What a sub-layer reads of the residual state ``x``, and what its
    output needs on the way back (``_residual``'s ``streams``): ``x`` as
    it is and None where a token has ONE residual vector; of several
    streams (..., n, dim) their mix under ``H_pre`` (..., dim), in
    float32, the n terms added in their order, rounded once — beside
    the streams themselves and the sub-layer's other two maps.  ``sub``:
    the sub-layer's leaves' prefix, "hc_attn" or "hc_mlp"."""
    if c.hc_mult == 1:
        return x, None
    with jax.named_scope("hc"):
        h_pre, h_post, h_res = hc_maps(
            layer[sub + "_phi"], layer[sub + "_b"], layer[sub + "_alpha"],
            x, c)
        wide = x.astype(jnp.float32)
        read = h_pre[..., 0, None] * wide[..., 0, :]
        for j in range(1, c.hc_mult):
            read = read + h_pre[..., j, None] * wide[..., j, :]
    return read.astype(x.dtype), (x, h_post, h_res)


def _norm(x, weight, c: LlamaConfig):
    """The block's and the head's norm, of the kind the config names."""
    if c.norm == "layer":
        return layernorm(x, weight, c.norm_eps)
    return rmsnorm(x, weight, c.norm_eps)


def _unconstrained(x, _dims):
    """``apply_block``'s ``constrain_act`` where no mesh lays the
    activations out."""
    return x


def _linear_inputs(layer: dict, h, c: LlamaConfig):
    """What a linear layer makes of ``h`` (..., dim) before anything
    runs along the sequence: ``u`` (..., heads * (2 * d_k + d_v)), the
    q, k and v projections side by side, not yet convolved; ``g``
    float32, the log-decays ``-exp(a_log) * softplus(. + dt_bias)`` —
    with ``linear_rank`` (..., heads, d_k), every key channel's own, a
    rate a head and a bias a channel around a low-rank projection
    ``w_fb (w_fa h)``; without it (..., heads), ONE a head, a rate and
    a bias a head around ``w_a h``; ``beta`` (..., heads)
    float32, the write strength ``2 * sigmoid(w_beta h)`` — up to 2, so
    that a write may turn a direction of the state over.  The one place
    they are made, for training, chunks and decode."""
    f32 = {"preferred_element_type": jnp.float32}
    heads = c.linear_heads
    u = jnp.concatenate([h @ layer[w] for w in ("wq", "wk", "wv")], axis=-1)
    step = jax.nn.softplus(
        (jnp.dot(h @ layer["w_fa"], layer["w_fb"], **f32) if c.linear_rank
         else jnp.dot(h, layer["w_a"], **f32))
        + layer["dt_bias"].astype(jnp.float32))
    rate = jnp.exp(layer["a_log"].astype(jnp.float32))
    g = (-rate[:, None] * step.reshape(*h.shape[:-1], heads, -1)
         if c.linear_rank else -rate * step)
    beta = 2.0 * jax.nn.sigmoid(jnp.dot(h, layer["w_beta"], **f32))
    return u, g, beta


def _ssm_inputs(layer: dict, h, c: LlamaConfig):
    """What a state-space layer makes of ``h`` (..., dim) before
    anything runs along the sequence, ONE product ``h @ in_proj`` with
    float32 sums, split: the gate ``z`` (..., heads * P) float32; ``u``
    (..., heads * P + 2 * N), the heads' inputs and the write and read
    directions side by side, not yet convolved, in the activations'
    dtype (as the cache keeps their tail); ``dt`` (..., heads) float32,
    the steps ``softplus(delta + dt_bias)``, not clamped.  The one place
    they are made, for training, chunks and decode."""
    inner, channels = ssm_widths(c)
    zxd = jnp.dot(h, layer["in_proj"], preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(zxd[..., inner + channels:]
                         + layer["dt_bias"].astype(jnp.float32))
    return zxd[..., :inner], zxd[..., inner:inner + channels].astype(
        h.dtype), dt


def _ssm_run(y, dt, weights: dict, c: LlamaConfig):
    """The convolution's output ``y`` (..., heads * P + 2 * N) float32
    -> ``ops/ssd.py``'s arguments up to the state: SiLU, then the heads'
    inputs x (..., heads, P), the steps, the heads' rates exp(a_log),
    the directions b and c (..., N), the skips."""
    y, inner = jax.nn.silu(y), ssm_widths(c)[0]
    return (y[..., :inner].reshape(*y.shape[:-1], c.ssm_heads, -1), dt,
            jnp.exp(weights["a_log"].astype(jnp.float32)),
            y[..., inner:inner + c.ssm_state], y[..., inner + c.ssm_state:],
            weights["d_skip"].astype(jnp.float32))


def _attend_ssm_rows(u, dt, weights: dict, c: LlamaConfig):
    """A state-space layer over ONE whole sequence from an empty state,
    no cache: u (seq, channels), dt (seq, heads) -> (seq, heads, P)
    float32.  The block form."""
    y, _ = delta_rule.causal_conv(
        u, jnp.zeros((c.ssm_conv - 1, u.shape[-1]), u.dtype),
        weights["conv_w"], weights["conv_b"])
    out, _ = ssd.chunk_ssd(*_ssm_run(y, dt, weights, c),
                           jnp.zeros(state_slabs(c)["s"][0]),
                           operand=c.dtype)
    return out


def _attend_conv_rows(u, conv_w, c: LlamaConfig):
    """A gated short-convolution layer over ONE whole sequence from an
    empty tail, no cache: u (seq, dim), the gated inputs -> (seq, dim)
    float32."""
    return delta_rule.causal_conv(
        u, jnp.zeros((c.conv_L_cache - 1, u.shape[-1]), u.dtype), conv_w)[0]


def _linear_qkv(y, c: LlamaConfig):
    """The convolution's output ``y`` (..., heads * (2 * d_k + d_v))
    float32 -> q, k (..., heads, d_k) and v (..., heads, d_v) float32:
    SiLU, then a head at a time q and k to unit length, q times
    d_k^-1/2 besides."""
    heads, width = c.linear_heads, c.linear_heads * c.linear_head_dim
    y = jax.nn.silu(y)
    if len(set(c.linear_widths)) == 1:           # three like parts
        y = y.reshape(*y.shape[:-1], 3, heads, -1)
        q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]
    else:
        q, k, v = (part.reshape(*y.shape[:-1], heads, -1) for part in (
            y[..., :width], y[..., width:2 * width], y[..., 2 * width:]))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * c.linear_head_dim ** -0.5, unit(k), v


def _delta_forms(c: LlamaConfig) -> tuple:
    """(the block form, the step form) of ``ops/delta_rule.py`` that a
    linear layer of this config runs: the channel forms where it states
    a low-rank decay (``linear_rank``), else those of one decay a
    head."""
    if c.linear_rank:
        return delta_rule.chunk_delta_rule, delta_rule.delta_rule_step
    return delta_rule.chunk_gdn, delta_rule.gdn_step


def _attend_linear_rows(u, g, beta, conv_w, c: LlamaConfig):
    """A linear layer over ONE whole sequence from an empty state, no
    cache: u (seq, heads * (2 * d_k + d_v)), g (seq, heads[, d_k]), beta
    (seq, heads) -> (seq, heads, d_v) float32.  The chunk form."""
    y, _ = delta_rule.causal_conv(
        u, jnp.zeros((c.linear_conv - 1, u.shape[-1]), u.dtype), conv_w)
    out, _ = _delta_forms(c)[0](
        *_linear_qkv(y, c), g, beta, jnp.zeros(state_slabs(c)["s"][0]))
    return out


def _qk_proj(layer: dict, h, c: LlamaConfig, step: bool = False):
    """The q and k projections of ``h`` (..., dim), still flat
    (..., heads * head_dim): with ``qk_norm`` each is RMS-normalised
    before the caller splits heads and applies RoPE — True: over its
    WHOLE width, all heads together, as OLMoE publishes it; "head":
    over each head's own ``head_dim`` values, one weight of that width
    for every head, as Qwen3's family publishes it.  The one place q/k
    are made and the one place the two forms are told apart, for
    training, chunks and decode; ``step`` as ``_proj`` takes it."""
    xq, xk = _proj(h, layer["wq"], step), _proj(h, layer["wk"], step)
    if c.qk_norm == "head":
        def by_head(x, weight):
            heads = x.reshape(*x.shape[:-1], -1, c.head_dim)
            return rmsnorm(heads, weight, c.norm_eps).reshape(x.shape)

        return by_head(xq, layer["q_norm"]), by_head(xk, layer["k_norm"])
    if c.qk_norm:
        xq = rmsnorm(xq, layer["q_norm"], c.norm_eps)
        xk = rmsnorm(xk, layer["k_norm"], c.norm_eps)
    return xq, xk


def _latent_qkv(layer: dict, h, c: LlamaConfig, cos, sin, positions,
                step: bool = False):
    """Latent attention's three products of ``h`` (..., dim), as
    DeepSeek-V2 (arXiv 2405.04434, section 2.1) publishes them: the
    queries ``xq`` (..., heads, nope + rope) through a low-rank
    projection with an RMSNorm inside, their last ``qk_rope_head_dim``
    rotated; the position's latent ``c_kv`` (..., kv_lora_rank), after
    its RMSNorm; and its rotary key ``k_rope`` (..., rope), rotated,
    ONE for all heads.  ``c_kv`` and ``k_rope`` are all a cache keeps of
    the position; per-head keys and values are ``c_kv @ w_kvb``, made
    (``_attend_latent_rows``) or absorbed (``_attend_slab``)
    where the scores are.  The one place they are made, for training,
    chunks and decode; ``step`` as ``_proj`` takes it, for the one of
    the three whose result is split into heads."""
    nope, rank = c.qk_nope_head_dim, c.kv_lora_rank
    xq = rmsnorm(h @ layer["w_qa"], layer["q_a_norm"], c.norm_eps)
    xq = _proj(xq, layer["w_qb"], step).reshape(
        *h.shape[:-1], c.n_heads, c.head_dim)
    xq = jnp.concatenate(
        [xq[..., :nope], apply_rope(xq[..., nope:], cos, sin, positions)],
        axis=-1)
    kv = h @ layer["w_kva"]
    c_kv = rmsnorm(kv[..., :rank], layer["kv_a_norm"], c.norm_eps)
    k_rope = apply_rope(kv[..., None, rank:], cos, sin, positions)[..., 0, :]
    return xq, c_kv, k_rope


def _kvb_by_head(w_kvb, c: LlamaConfig):
    """``w_kvb`` (rank, heads * (nope + v)) -> its keys' part (rank,
    heads, nope) and its values' part (rank, heads, v)."""
    w = w_kvb.reshape(c.kv_lora_rank, c.n_heads, -1)
    return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]


def _attend_latent_rows(xq, c_kv, k_rope, w_kvb, c: LlamaConfig):
    """Causal latent attention of whole sequences over themselves, no
    cache, in the published per-head form: every position's keys and
    values are made from its latent.  xq (b, s, heads, nope + rope),
    c_kv (b, s, rank), k_rope (b, s, rope) -> (b, s, heads, v).  Plain
    products with a float32 softmax (the flash kernel takes one width
    for q, k and v)."""
    wk, wv = _kvb_by_head(w_kvb, c)
    k = jnp.einsum("bsc,chd->bshd", c_kv, wk)
    v = jnp.einsum("bsc,chd->bshd", c_kv, wv)
    k = jnp.concatenate([k, jnp.broadcast_to(
        k_rope[:, :, None, :], (*k.shape[:-1], k_rope.shape[-1]))], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", xq, k,
                        preferred_element_type=jnp.float32) * c.attn_scale
    seq = xq.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(xq.dtype)


def _attend_block_rows(xq, xk, xv, c: LlamaConfig):
    """BLOCK-causal attention of whole sequences over themselves, no
    cache (``LlamaConfig.block_length``): position ``t`` sees ``s`` iff
    ``s // block_length <= t // block_length``.  xq (b, s, heads, hd),
    xk / xv (b, s, kv_heads, hd) -> (b, s, heads, hd).  Plain products
    with a float32 softmax, as ``_attend_latent_rows``: the flash and
    the blockwise kernels mask by position, not by block."""
    b, seq = xq.shape[:2]
    q = xq.reshape(b, seq, c.n_kv_heads, -1, c.head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, xk,
                        preferred_element_type=jnp.float32) * (
        c.attention_multiplier or c.head_dim ** -0.5)
    blocks = jnp.arange(seq) // c.block_length
    scores = jnp.where(blocks[None, :] <= blocks[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(xv.dtype), xv,
                     preferred_element_type=jnp.float32)
    return out.reshape(xq.shape).astype(xq.dtype)


def _rounded(x, dtype):
    """float32 ``x`` rounded to ``dtype`` by an operation of its own,
    which no compiler setting takes out (a bare ``astype`` it may)."""
    info = jnp.finfo(dtype)
    return lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


def _proj(h, w, step: bool):
    """``h @ w``.  ``step``: in a step program the product's float32
    sums and their one rounding to the activations' dtype are spelt
    out — what the bare product in bfloat16 means, no other precision.
    Two things hang on the spelling there.  The weight is a layer's
    slice of its stack: before a reshape to heads the TPU compiler
    gives the bare product a heads-major result, wants the weight
    transposed for it, and so slices the layer out of the stack and
    copies it again, a layer a step (Mistral-7B: 48 MiB twice, 2 ms of
    a 12.6 ms decode step; PERF.md section 6, PR 44); behind the named
    rounding the slice stays inside the product's fusion, as every
    other product of the block has it.  And the compiler may keep a
    bare product's sums unrounded into the operation it fuses it with
    (``_swiglu``), which one depending on the rows.  Training keeps the
    bare form."""
    if not step:
        return h @ w
    return _rounded(jnp.dot(h, w, preferred_element_type=jnp.float32),
                    h.dtype)


def _swiglu(h, w_gate, w_up, w_down, step: bool = False):
    """``step``: in a step program the gate's and the up product's
    rounding to the activations' dtype is spelt out (``_proj``).  The
    TPU compiler fuses ONE of the two products with the elementwise
    tail and then keeps that one's float32 sums unrounded (its "excess
    precision"); which one depends on the rows — the gate's for 12
    decode rows, the up product's for 76 — so a row's bits depended on
    the program it went through (InternLM2: decode logits 2.6e-2 off,
    PERF.md section 6, PR 39).  Rounded by name both are what the
    equations say, in every program.  Training keeps the bare form."""
    return (jax.nn.silu(_proj(h, w_gate, step)) * _proj(h, w_up, step)
            ) @ w_down


def _mlp(layer: dict, h, c: LlamaConfig, index=None, tile=0, live=None):
    """The block's feed-forward on ``h`` (..., dim): dense SwiGLU, or
    the routed experts, with the shared expert beside them where the
    model has one.  Returns ``(out, load)``; ``load`` is the
    (num_experts,) int32 count of rows each expert held was given, None
    for a dense layer.  The one MLP of training, chunks and decode; a
    step program's (it alone passes ``index``) spells its roundings out
    (``_swiglu``) and says which rows are ``live`` — to the routed
    experts alone, whose bytes follow the rows (``_routed_mlp``): a
    dense product reads its weights once whatever the rows."""
    if not c.num_experts:
        return _swiglu(h, layer["w_gate"], layer["w_up"],
                       layer["w_down"], index is not None), None
    out, load = _routed_mlp(layer, h, c, index, tile, live)
    if c.n_shared_experts:
        with jax.named_scope("moe_shared"):
            # one SwiGLU as wide as all the shared experts together is
            # the sum of theirs; averaged, that sum over their number
            shared = _swiglu(h, layer["shared_gate"], layer["shared_up"],
                             layer["shared_down"], index is not None)
            if c.shared_experts_average:
                shared = shared / c.n_shared_experts
            out = out + shared
    return out, load


def _routed_mlp(layer: dict, h, c: LlamaConfig, index=None, tile=0,
                live=None):
    """Top-k mixture of experts; every token is computed by those of its
    k experts that are held here, and none is dropped.

    The router's scores are float32, a softmax over ALL experts or
    (``router_scoring`` "sigmoid") each expert's own sigmoid; the gates
    are the k largest (divided by their sum only when
    ``norm_topk_prob``), times ``routed_scaling_factor``.  The tokens *
    k (token, expert) assignments are sorted by expert, so each expert's
    rows lie together, and a GROUPED PRODUCT multiplies each run of rows
    with its expert's matrix: its operations are those of k experts a
    token, and it reads an expert's weights only if it has a row.  The
    way back to token order is ONE pass over the down product's float32
    rows: each token gathers the k rows that are its own, a held one
    under its float32 gate and one of no group as zero, and adds them
    in the picks' order — the (tokens * k, dim) array is read once and
    never written again in token order.  ``_back_to_tokens`` is that
    equation in plain XLA; where the grouped product is the Pallas
    kernel, so is the way back (``ops/pallas/gather_sum.py``: the same
    select, gates, k adds and rounding in one call, where XLA leaves 2 k
    small operations a layer).  Shapes are static (tokens * k rows
    whatever the routing), so one formulation serves the training step,
    a prefill chunk and a decode step.

    Which grouped product runs where (bf16 operands, float32 sums and
    float32 out in both; the same mathematics):

    * ``lax.ragged_dot`` — ``forward`` / ``loss_fn`` (it differentiates
      as written), every backend but the TPU (masked dense products, the
      same values) and a stack sharded over a mesh (the partitioner
      splits it by expert over ``ep``): ``_grouped_tile`` says which.
      On the TPU XLA compiles it to a
      grouped-matmul kernel whose row tile is the OPERAND's row count:
      every expert hit pays for all tokens * k rows, its own or not.
    * ``ops/pallas/grouped_matmul.py`` (``tile`` > 0: its row tile;
      with ``index``) — the step programs on one TPU device.  Its work list visits (row
      tile, expert) pairs that hold a row of a held expert, in tiles of
      about the rows an expert really gets, so a chunk or a decode step
      of a share stops multiplying tiles of masked rows; a row's sum is
      its own whatever rows share its tile.

    Three kinds of assignment belong to NO GROUP: they are labelled
    ``num_experts``, sort behind the last group and are dropped by the
    count, so the grouped product's sizes add up to the assignments
    that are somebody's — it neither reads a weight nor multiplies for
    the others — and they count zero in the sum, by a select on their
    row's place (the product's output holds there whatever the buffer
    held).

    * An ABSENT expert's.  Where the router is wider than the experts
      held (``router_width`` against ``num_experts`` from
      ``first_expert`` on), the routing is over all of them and the
      products over the held ones.  Nothing stands in for the absent
      experts.
    * An IDLE SLOT's.  A decode step computes one row a slot, and the
      row of a slot that is not active is whatever its stale token
      makes of it: routed like any other it would fall on experts no
      live row asked for, and their weights would be read for a
      product nobody reads.
    * A chunk's PADDING rows' (behind the prompt's last token) and a
      block step's DEAD CLOSING rows' (a slot with no block closing).

    ``live`` ((rows,) bool, None: every row) says which rows anybody
    reads; the step programs pass it (``_row_groups``), training does
    not.  All k picks of a row that is not live are of no group: the
    row comes back as zeros, and every live row's sum is bit for bit
    what it is with no mask — a row's products are its own whatever
    shares its tile, the stable sort keeps the live rows' order within
    an expert, and a token's picks are added in the picks' order.

    ``layer``'s expert matrices are one layer's (experts, in, out), as
    a scan over the stacked layers slices them — or, with ``index``,
    the whole stack's (layers, experts, in, out), of which layer
    ``index`` (traced) is meant: XLA's kernel then reads the stack as
    layers * experts groups, all empty but that layer's, the Pallas
    kernel takes the layer by scalar prefetch.  The step programs do so
    (``_scan_layers``), because a slice of the stack cannot be fused
    into a grouped kernel's operand: sliced, every step would first
    copy every expert's weights, hit or not.
    """
    lead, dim = h.shape[:-1], h.shape[-1]
    k, n_exp = c.experts_per_token, c.num_experts
    share = bool(c.router_width) and c.router_width != n_exp
    with jax.named_scope("moe"):
        x = h.reshape(-1, dim)
        logits = jnp.dot(x, layer["router"],
                         preferred_element_type=jnp.float32)
        scores = (jax.nn.sigmoid(logits) if c.router_scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        if c.router_bias:
            # the bias picks and does not weigh
            _, experts = lax.top_k(
                scores + layer["router_bias"].astype(jnp.float32), k)
            gates = jnp.take_along_axis(scores, experts, axis=-1)
        else:
            gates, experts = lax.top_k(scores, k)          # (tokens, k)
        if c.norm_topk_prob:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        if c.routed_scaling_factor != 1.0:
            gates = gates * c.routed_scaling_factor
        experts = experts.reshape(-1)                      # (tokens * k,)
        if share:
            # held experts 0..n_exp-1; every absent one n_exp, which the
            # sort puts last and the count below drops (out of bounds)
            experts = experts - c.first_expert
            experts = jnp.where((experts >= 0) & (experts < n_exp),
                                experts, n_exp)
        if live is not None:
            # a row nobody reads: its k picks are of no group either
            experts = jnp.where(jnp.repeat(live.reshape(-1), k), experts,
                                n_exp)
        order = jnp.argsort(experts)                       # stable
        load = jnp.zeros((n_exp,), jnp.int32).at[experts].add(1)
        rows = x[order // k]                               # sorted by expert
        if tile:
            def grouped(a, w):
                return grouped_matmul.grouped_matmul(
                    a, w, load, index, tiling=(tile,) + grouped_matmul.panel(
                        *w.shape[-2:], w.dtype.itemsize),
                    interpret=jax.default_backend() != "tpu")
        else:
            sizes = load if index is None else lax.dynamic_update_slice(
                jnp.zeros((layer["w_down"].shape[0] * n_exp,), jnp.int32),
                load, (index * n_exp,))

            def grouped(a, w):
                return lax.ragged_dot(a, w.reshape(-1, *w.shape[-2:]), sizes,
                                      preferred_element_type=jnp.float32)

        gated = jax.nn.silu(grouped(rows, layer["w_gate"])) * grouped(
            rows, layer["w_up"])
        down = grouped(gated.astype(h.dtype), layer["w_down"])
        # token t's pick j is sorted row back[t, j], held if below held
        back, held = jnp.argsort(order).reshape(-1, k), jnp.sum(load)
        if tile:
            out = gather_sum.gather_sum(
                down, back, gates, held, dtype=h.dtype,
                interpret=jax.default_backend() != "tpu")
        else:
            out = _back_to_tokens(down, back, gates, held, h.dtype)
    return out.reshape(*lead, dim), load


def _back_to_tokens(down, back, gates, held, dtype):
    """The grouped down product's rows ``down`` (tokens * k, dim)
    float32, sorted by expert, back in token order under their gates:
    ``sum_j select(back[t, j] < held, down[back[t, j]], 0) * gates[t,
    j]`` -> (tokens, dim) ``dtype``.  A row from ``held`` on belongs to
    no group and is whatever the product left there, a NaN too: a SELECT
    drops it, before the gate (a product with 0 would keep the NaN, and
    so would the gate's gradient).  The k terms are added in float32 in
    the picks' order, spelt as k adds — a sum over an axis is added in
    an order the compiler takes from the operand's shape, and a row's
    bits must not depend on its company (PERF.md section 6, PR 39, PR
    53)."""
    out = None
    for j in range(back.shape[1]):
        rows = back[:, j]
        term = jnp.where((rows < held)[:, None], down[rows],
                         0.0) * gates[:, j, None]
        out = term if out is None else out + term
    return out.astype(dtype)


def _rope_tables(c: LlamaConfig, positions: int | None = None):
    """cos and sin (positions, rope_dim / 2) float32 of what a head
    rotates; ``positions``: as many as the caller can reach (a step
    program: its cache's), by default the model's ``max_seq``."""
    return rope_frequencies(c.rope_dim, positions or c.max_seq,
                            c.rope_theta, jnp.float32, c.rope_scaling)


def _stacks(params: dict, c: LlamaConfig) -> list:
    """``[(a run of layers' stacked leaves BY KIND, the config that
    reads them)]``, in the layers' order (``LlamaConfig.stacks``): the
    softmax layers' stack under "layers" where the run has any, and
    beside it the recurrent layers' (``LINEAR``, ``SSM`` or ``CONV``)
    where it has any (``LlamaConfig.place`` says which a place of the
    period reads, ``run_stacks`` where each lies in ``params``)."""
    return [({stack: params[lies] for stack, lies in run_stacks(
        name, cfg).items()}, cfg) for name, cfg in c.stacks().items()]


def _by_period(stacks: dict, c: LlamaConfig):
    """A run's stacks (``_stacks``), leaves (layers, ...), as a scan
    over the layer pattern's periods takes them: ``(what it scans, what
    it closes over)``.  A
    model whose layers are all alike (a period of one) is scanned layer
    by layer, as it lies.  Unlike layers are not sliced by the scan at
    all: the body takes each place's layer out of the whole stack where
    it lies (``_place``) — a period's slice, sliced again by place, is a
    copy of every weight of the period on every call."""
    if len(c.kinds) == 1:
        return stacks[c.place(0)[0]], None
    return jnp.arange(c.n_layers // len(c.kinds)), stacks


def _place(scanned, whole, j: int, c: LlamaConfig) -> dict:
    """The layer at place ``j`` of the period a scan over ``_by_period``
    is at, out of the stack of its kind."""
    if whole is None:
        return scanned
    name, a_period, rank = c.place(j)
    return jax.tree.map(lambda leaf: lax.dynamic_index_in_dim(
        leaf, scanned * a_period + rank, keepdims=False), whole[name])


def _unperiod(scanned, c: LlamaConfig):
    """What a scan over periods stacked per place, (periods, places,
    ...), back in the layers' order (layers, ...)."""
    if len(c.kinds) == 1:
        return scanned
    return jax.tree.map(
        lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), scanned)


def _checkpointed(block, remat: str):
    """``block`` under ``forward``'s ``remat`` policy."""
    if remat == "full":
        return jax.checkpoint(block)
    if remat == "dots":
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat == "matmuls":
        # Saves every matmul output (batch dims included) plus the flash
        # kernel's named residuals (attention output + logsumexp) — in a
        # transformer block that is all the expensive ops, so backward
        # recomputes only the elementwise tail and never re-runs the
        # attention forward.  ~3× the activation HBM of "full",
        # near-"none" step time; the single-chip bench sweet spot when
        # "none" OOMs.
        from ant_ray_tpu.ops.attention import saveable_attention_policy  # noqa: PLC0415

        return jax.checkpoint(block, policy=saveable_attention_policy())
    if remat != "none":
        raise ValueError(f"unknown remat policy {remat!r}")
    return block


def forward(params: dict, tokens, config: LlamaConfig, *, mesh=None,
            attn_impl: str = "auto", positions=None, remat: str = "full"):
    """tokens: (batch, seq) int32 → logits (batch, seq, vocab) fp32.

    When ``mesh`` is provided, activations get sharding constraints
    (batch over dp/fsdp, seq over sp, heads over tp) and sequence-sharded
    meshes use ring attention.

    ``remat`` trades HBM for recompute FLOPs in the backward pass:
    "full" (checkpoint every block — the multi-chip/8B default), "dots"
    (save matmul outputs, recompute the cheap elementwise tail), "none"
    (save everything — best MFU when the model fits, e.g. the single-chip
    bench).
    """
    c = config
    if c.hc_mult > 1 and mesh is not None:
        raise ValueError(HC_NO_MESH)
    scale = c.attention_multiplier or None       # None: head_dim^-1/2
    cos, sin = _rope_tables(c)
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1

    def constrain_act(x, dims):
        if mesh is None:
            return x
        from jax.sharding import NamedSharding  # noqa: PLC0415

        spec = logical_to_spec(dims, llama_rules())
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def attend_state(rows, *inputs):
        # no cache: every sequence from an empty state; the last input
        # is the layer's own leaves, the others are a sequence's
        return jax.vmap(functools.partial(rows, c=c), (
            *(0,) * (len(inputs) - 1), None))(*inputs), None

    def attend(window, xq, xk, xv, w_kvb=None):
        # no cache: the whole sequence attends over itself
        if w_kvb is not None:       # latent: xk, xv are c_kv and k_rope
            out = _attend_latent_rows(xq, xk, xv, w_kvb, c)
        elif c.block_length:
            if use_ring:
                raise ValueError("ring (sequence-parallel) attention "
                                 "computes no block-causal mask "
                                 "(block_length)")
            out = _attend_block_rows(xq, xk, xv, c)
        elif window:
            # the flash kernel takes no window mask: the blockwise path
            # is named for a window layer, never swapped in silently
            if use_ring:
                raise ValueError("ring (sequence-parallel) attention "
                                 "computes no sliding window")
            out = attention(xq, xk, xv, causal=True, impl="blockwise",
                            window=window, scale=scale)
        elif use_ring:
            from ant_ray_tpu.parallel.ring import ring_attention  # noqa: PLC0415

            out = ring_attention(xq, xk, xv, mesh=mesh, causal=True,
                                 scale=scale)
        elif mesh is None:
            out = attention(xq, xk, xv, causal=True, impl=attn_impl,
                            scale=scale)
        else:
            rules = llama_rules()
            out = attention(
                xq, xk, xv, causal=True, impl=attn_impl, mesh=mesh,
                scale=scale,
                q_spec=logical_to_spec(
                    ("batch", "seq", "heads", "head_dim"), rules),
                kv_spec=logical_to_spec(
                    ("batch", "seq", "kv_heads", "head_dim"), rules))
        return out, None

    state_rows = {"linear": _attend_linear_rows, "ssm": _attend_ssm_rows,
                  "conv": _attend_conv_rows}

    def scan_stack(x, stack, cfg):
        scanned, whole = _by_period(stack, cfg)

        def period(x, layers):
            # the period's unlike layers, each with its own way to attend
            for j, kind in enumerate(cfg.kinds):
                x, _, _ = apply_block(
                    _place(layers, whole, j, cfg), x, cfg, cos, sin,
                    positions,
                    functools.partial(attend_state, state_rows[kind])
                    if kind in RECURRENT else functools.partial(
                        attend, cfg.window if kind == "window" else 0),
                    constrain_act, kind=kind)
            return x, None

        return lax.scan(_checkpointed(period, remat), x, scanned)[0]

    x = _embed(params, tokens, c)
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

    def layers(x):
        # the layers once, then the final norm
        for stack, cfg in _stacks(params, c):
            x = scan_stack(x, stack, cfg)
        return _norm(_joined(x, c), params["norm_f"], c)

    def a_pass(x, _):
        with jax.named_scope("loop_pass"):
            return constrain_act(layers(x), ("batch", "seq", "embed")), None

    # a looped model's passes are a loop in the program, the same
    # weights every pass, the norm closing each; the head reads the
    # last pass's normed state
    x = layers(x) if c.loops == 1 else lax.scan(
        a_pass, x, None, length=c.loops)[0]
    return constrain_act(_head(params, x, c), ("batch", "seq", None))


HC_NO_MESH = (
    "several residual streams (hc_mult) are not laid out over a mesh: tp "
    "splits dim, and the maps' norm over all streams would need a "
    "reduction over it that is not written (no mesh, "
    "tensor_parallel_size=1)")


def _refuse_block_training(c: LlamaConfig):
    if c.block_length:
        raise ValueError(
            "block_length (generation by diffusion over blocks) is trained "
            "on a noised copy of each block beside the clean sequence: "
            "that objective is not written, and the next-token loss is "
            "not this model's (logits at a place predict the token AT it)")


def loss_fn(params: dict, batch: dict, config: LlamaConfig, *, mesh=None,
            attn_impl: str = "auto", remat: str = "full"):
    """batch: {"tokens": (b, s+1) int32} — next-token cross entropy."""
    _refuse_block_training(config)
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
    _refuse_block_training(c)
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    pp = mesh.shape["pp"]
    if c.n_layers % pp != 0:
        raise ValueError(f"n_layers {c.n_layers} % pp {pp} != 0")
    if c.n_dense_layers or c.kv_lora_rank or c.window or c.recurrent:
        raise ValueError("the pipeline schedule runs one stack of like "
                         "grouped-query layers: no leading dense layers, "
                         "no latent attention, no window layers, no "
                         "linear layers, no ssm layers, no conv layers")
    if c.loops > 1:
        raise ValueError("the pipeline schedule runs its stages' layers "
                         "once: loops (a looped stack) are not computed")
    if c.hc_mult > 1:
        raise ValueError("the pipeline schedule hands ONE residual vector "
                         "a token from stage to stage: several residual "
                         "streams (hc_mult) are not computed")
    cos, sin = _rope_tables(c)

    def attend(xq, xk, xv):
        return attention(xq, xk, xv, causal=True, impl=attn_impl,
                         scale=c.attention_multiplier or None), None

    def stage_fn(stage_layers, mx):
        def body(h, layer):
            h, _, _ = apply_block(layer, h, c, cos, sin, None, attend,
                                  _unconstrained)
            return h, None

        out, _ = lax.scan(body, mx, stage_layers)
        return out

    x = _embed(params, inputs, c)                        # (b, s, d)
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
    logits = _logits(params, x, c)
    import optax  # noqa: PLC0415

    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(logits, targets))


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token (6·N matmul + attention quadratic term); of
    a routed model's experts N holds the k a token multiplies with — of
    a share of them (``router_width``), the part of k that falls on
    it when the router is even."""
    c = config
    active = c.experts_per_token * c.num_experts / (
        c.router_width or c.num_experts or 1)
    idle = max(c.num_experts - active, 0)
    matmul = 6 * (c.num_params() - (c.n_layers - c.n_dense_layers)
                  * idle * 3 * c.dim * c.mlp_dim)
    if c.loops > 1:
        # every pass multiplies with the layers' matrices again
        matmul += 6 * (c.loops - 1) * sum(
            math.prod(shape) for shape in param_shapes(c)["layers"].values())
    # the softmax layers' scores and sums (a recurrent layer has none:
    # a conv layer's taps are among the parameters, a multiply-add a
    # token each)
    attn = 6 * c.loops * (c.n_layers - c.n_recurrent) * c.n_heads * seq_len \
        * (c.head_dim + (c.v_head_dim or c.head_dim))
    if c.hc_mult > 1:
        # a sub-layer's read, write and mix of the streams (their maps'
        # product is among the parameters)
        n = c.hc_mult
        matmul += 6 * c.n_layers * 2 * (n * n + 2 * n) * c.dim
    return matmul + attn


# ------------------------------------------------------------- kv cache
# Serving-path primitives (ref capability: llm/_internal/serve/engines/
# vllm — re-designed TPU-first: dense per-slot KV slabs with static
# shapes instead of paged indirection, because XLA wants static shapes
# and HBM slabs keep the decode matmuls MXU-friendly).  The slabs are
# cheap only while nothing copies them (moved about, they were 59 % of
# the step programs' device time on a v5e): the step programs carry them
# through the layer loop and write the new rows in place
# (``_scan_layers``).  All three — a chunk, a decode step, and the two
# as one (``mixed_step``) — run ``apply_block``; what is theirs is which
# rows they write and which slab they attend over (``_row_groups``).

def kv_slabs(config: LlamaConfig) -> dict:
    """What a layer keeps of a position: the cache's slab leaves by
    name, each with the shape of one position.  Keys and values per KV
    head — or, with latent attention, the position's latent ``c_kv``
    (after its norm) and its one rotary key ``k_rope`` (rotated),
    ``kv_lora_rank + qk_rope_head_dim`` values and no heads axis.  A
    model with window layers has a second group, ``k_ring`` / ``v_ring``:
    its full layers keep every position in ``k`` / ``v``, its window
    layers the newest ``ring_positions`` in a ring.  Where the KV heads
    do not fill whole sublane tiles (``LlamaConfig.flat_kv_heads``) a
    position holds them side by side, one axis of ``n_kv_heads *
    head_dim`` values, and a block is split into heads where it is
    attended over (``_attend_slab``)."""
    c = config
    if c.kv_lora_rank:
        return {"c_kv": (c.kv_lora_rank,), "k_rope": (c.qk_rope_head_dim,)}
    position = ((c.n_kv_heads * c.head_dim,) if c.flat_kv_heads
                else (c.n_kv_heads, c.head_dim))
    return {"k": position, "v": position,
            **({"k_ring": position, "v_ring": position} if c.window else {})}


def state_slabs(config: LlamaConfig) -> dict:
    """What a RECURRENT layer keeps of a sequence — a slot's, whatever
    its length: the cache's state leaves by name, each (its shape a
    slot, its dtype), under the same names for every kind.  ``s``:
    the state, float32 (it is summed into over the whole sequence) — a
    linear layer's (d_k, d_v) a head (``linear_widths``: not always
    square), a state-space layer's (P, N) a
    head; a gated short-convolution layer has NONE, and no ``s``;
    ``conv``: the last ``taps - 1`` inputs of the layer's
    convolution — over q, k and v, over the heads' inputs and the
    two directions, or over the gated input ``B * u`` — as the block
    made them, in the weights' dtype.  Empty without such
    layers.  Not among ``kv_slabs``, whose third axis is positions:
    these have none."""
    c = config
    if c.recurrent == "conv":
        return {"conv": ((c.conv_L_cache - 1, c.dim), c.dtype)}
    if c.recurrent == "ssm":
        return {"s": ((c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                      jnp.float32),
                "conv": ((c.ssm_conv - 1, ssm_widths(c)[1]), c.dtype)}
    if not c.n_linear:
        return {}
    d_k, d_v = c.linear_widths
    return {"s": ((c.linear_heads, d_k, d_v), jnp.float32),
            "conv": ((c.linear_conv - 1, c.linear_heads * (2 * d_k + d_v)),
                     c.dtype)}


def ring_positions(config: LlamaConfig, max_seq: int, chunk: int = 0) -> int:
    """Rows of a window layer's ring in a ``max_seq``-position cache
    whose prompts arrive in chunks of ``chunk`` tokens (0: never, a
    token at a time): position ``p`` lies at row ``p mod ring``.  The
    step programs write a call's rows and only THEN attend
    (``_scan_layers``), so the ring holds the window AND the chunk: in
    one of exactly ``window`` rows a chunk's later tokens would
    overwrite keys its first queries still see.  0 without window
    layers."""
    if not config.window:
        return 0
    return min(config.window + chunk, max_seq)


def init_kv_cache(config: LlamaConfig, slots: int,
                  max_seq: int | None = None, chunk: int = 0) -> dict:
    """Per-slot dense slabs (layers, slots, max_seq, *position), one for
    each of ``kv_slabs``; all ``n_layers`` of a model lie in one slab,
    its leading dense layers first — or, of a model with window layers,
    the full layers in (full layers, slots, max_seq, ...) and the window
    layers in rings (window layers, slots, ``ring_positions``, ...),
    both under ONE ``length``; ``chunk`` is the width its prompts are
    ingested in.  A model's recurrent layers keep no positions but
    ``state_slabs`` (recurrent layers, slots, ...), under the same
    ``length``; its full layers alone have slabs.  A looped model
    has a slab layer for every (pass, layer) pair, ``loops`` times its
    layers (``LlamaConfig.slab_layers``), pass ``u``'s behind pass
    ``u - 1``'s.  A routed model's
    cache also carries ``routing``, the
    step programs' running counters (``ROUTING_COUNTERS``), a model
    with an exit gate ``exits`` (``EXIT_COUNTERS``).

    Whoever jits a step program owns these buffers and DONATES them
    (``llm/engine.py``: ``donate_argnums=(1,)``): every leaf of the
    cache a program returns is then the buffer it was given, rows
    written where they lie, and the dict passed in is dead.  Without
    the donation a call allocates and fills a second whole cache."""
    c = config
    ms = max_seq or c.max_seq
    n_window, n_full = c.slab_layers()
    ring = ring_positions(c, ms, chunk)
    cache = {name: jnp.zeros(
        ((n_window, slots, ring) if name.endswith("_ring")
         else (n_full, slots, ms)) + position, c.dtype)
        for name, position in kv_slabs(c).items()}
    for name, (shape, dtype) in state_slabs(c).items():
        cache[name] = jnp.zeros((c.n_recurrent, slots) + shape, dtype)
    # tokens already written per slot (== next write position)
    cache["length"] = jnp.zeros((slots,), jnp.int32)
    if c.num_experts:
        cache["routing"] = jnp.zeros((len(ROUTING_COUNTERS),), jnp.uint32)
    if c.exit_gate:
        cache["exits"] = jnp.zeros((len(EXIT_COUNTERS),), jnp.uint32)
    return cache


# What the step programs count of a looped model's exit gate, summed
# over executions in ``cache["exits"]`` (uint32, wraps; a reader takes
# differences), of a decode step's ACTIVE rows alone: the rows, and the
# sum of their expected exit pass ``sum_u (u + 1) p_u`` (1 .. loops)
# under the exit distribution the gates give, in units of
# ``EXIT_PASS_UNIT`` of a pass, rounded once a step.
EXIT_COUNTERS = ("exit_rows", "exit_pass_sum")
EXIT_PASS_UNIT = 1 / 1024


def exit_distribution(gates):
    """The gates ``lambda_u`` (loops, rows) of a looped model's passes
    -> the probability (loops, rows) that a row leaves behind pass
    ``u``: ``p_u = lambda_u * prod_{j<u} (1 - lambda_j)``, the last
    pass's the remainder."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def _count_exits(cache: dict, gates, counted) -> dict:
    """``gates``: (loops, rows) float32 of one execution, None without
    an exit gate; ``counted``: (rows,) bool, its decode rows that are
    active, None where it has none -> the cache entry to carry."""
    if gates is None:
        return {}
    if counted is None:
        return {"exits": cache["exits"]}
    passes = jnp.arange(1, gates.shape[0] + 1, dtype=jnp.float32)
    expected = jnp.sum(passes[:, None] * exit_distribution(gates), axis=0)
    seen = jnp.stack([
        jnp.sum(counted).astype(jnp.float32),
        jnp.round(jnp.sum(jnp.where(counted, expected, 0.0))
                  / EXIT_PASS_UNIT)])
    return {"exits": cache["exits"] + seen.astype(jnp.uint32)}


# What the step programs count of a routed
# model's routing, summed over layers and executions in
# ``cache["routing"]`` (uint32, wraps; a reader takes differences).  They
# count the LIVE rows' pairs — the rows somebody reads: an active slot's,
# a chunk's real tokens', a closing block's (``_row_groups``) — for those
# alone reach an expert and decide which expert weights a step reads; the
# pairs of the other rows a program computes are ``moe_dead_pairs``.  The
# first four are over the experts HELD (all of them, unless the model is
# a share).
ROUTING_COUNTERS = (
    "moe_assignments",    # (live row, expert) pairs computed
    "moe_experts_hit",    # experts given at least one row
    "moe_expert_slots",   # experts there were: num_experts a layer
    "moe_load_max",       # rows of each layer's busiest expert, summed
    "moe_rows_routed",    # (live row, expert) pairs routed: live rows * k,
                          # on an expert held or not
    # The same of the decode steps alone: a chunk's rows are ONE
    # sequence's and route alike, so what a decode step reads cannot be
    # told from counters that a window's share of chunks moves.  A
    # mixed step carries a chunk: it counts above, not here.
    "moe_decode_assignments", "moe_decode_experts_hit",
    "moe_decode_expert_slots", "moe_decode_rows_routed",
    # rows of the row tiles the grouped kernel visited (its work list's
    # length times its tile, a layer): what it multiplied, where
    # ``moe_assignments`` is what it was asked; 0 under XLA's kernel
    "moe_tile_rows",
    # (row, expert) pairs of the rows that were NOT live — idle slots,
    # padding, dead closing rows: rows * k of them a layer, which reached
    # no expert.  Over these plus ``moe_rows_routed``: the share of a
    # program's rows the routed experts were spared
    "moe_dead_pairs",
    # the expert matrices the grouped kernel FETCHED, over a layer's
    # three products (``grouped_matmul.fetches``): a product's visits
    # where its k is in tiles, its experts hit where an expert is one
    # block.  Over 3 * ``moe_experts_hit``: 1.0 where every expert hit
    # is read once a product; 0 under XLA's kernel
    "moe_expert_reads",
    # of those, the fetches the kernel started under an EARLIER group's
    # crossing visit (``grouped_matmul.fetches_ahead``: an expert that is
    # one block is fetched by group, the next one from the current one's
    # first visit on).  Over ``moe_expert_reads``: the share of fetches
    # that no longer wait for their group's turn — ~0 in a decode step,
    # whose groups are one visit each; 0 where k is in tiles
    "moe_fetches_ahead",
)


def _grouped_tile(c: LlamaConfig, rows: int, mesh) -> int:
    """The row tile with which a step program of ``rows`` rows runs its
    routed experts through ``ops/pallas/grouped_matmul.py``; 0: it keeps
    ``lax.ragged_dot`` — a dense model, any backend but the TPU, and a
    ``mesh`` (a Mosaic kernel is not partitioned automatically, and the
    partitioner splits XLA's product by expert).  The tile follows the
    rows an expert gets under an even router: rows * k over the
    router's width."""
    if not c.num_experts or mesh is not None \
            or jax.default_backend() != "tpu":
        return 0
    return grouped_matmul.row_tile(
        rows * c.experts_per_token / (c.router_width or c.num_experts))


def _hoist_experts(layers: dict, c: LlamaConfig, stack: str = "layers"):
    """A stack of layers (``stack``: its name, as ``place`` gives it) as
    ``_scan_layers``' scan takes it: ``(the
    leaves it slices layer by layer (``_by_period``), the expert
    matrices it closes over whole, the period indices it scans beside
    them)`` — see ``_routed_mlp`` on why; a dense stack's layers are all
    sliced."""
    a_period = sum(RECURRENT.get(kind, "layers") == stack
                   for kind in c.kinds)
    index = jnp.arange(layers["ln_attn"].shape[0] // a_period)
    if not c.num_experts:
        return layers, {}, index
    whole = {name: layers[name] for name in ("w_gate", "w_up", "w_down")}
    sliced = {name: leaf for name, leaf in layers.items()
              if name not in whole}
    return sliced, whole, index


def _count_routing(cache: dict, loads, routed, decode: bool,
                   tile: int = 0, dead=0, expert=None) -> dict:
    """``loads``: (layers, num_experts) rows per expert held of one
    execution, None for a dense model; ``routed``: the (row, expert)
    pairs its routers made for live rows, held or not, and ``dead``
    those of its other rows; ``decode``: the execution is a
    decode step; ``tile``: the grouped kernel's row tile, 0 under XLA's
    kernel, and with a tile ``expert``, what it multiplies by: an
    expert's (dim, width, bytes an element) -> the cache entries to
    carry."""
    if loads is None:
        return {}
    seen = jnp.stack([jnp.sum(loads), jnp.sum(loads > 0), loads.size,
                      jnp.sum(jnp.max(loads, axis=-1)), routed])
    apart = seen[jnp.array([0, 1, 2, 4])]
    visited = reads = ahead = 0
    if tile:
        def over_layers(count, *rule):
            """the kernel's own rule, layer by layer"""
            return jnp.sum(jax.vmap(lambda sizes: count(sizes, tile, *rule))(
                loads))

        def three_products(count):
            """gate and up (dim, wide), down (wide, dim)"""
            def of(k, n):
                return over_layers(
                    count, k // grouped_matmul.panel(k, n, itemsize)[0])
            return 2 * of(dim, wide) + of(wide, dim)

        dim, wide, itemsize = expert
        visited = tile * over_layers(grouped_matmul.visits)
        reads = three_products(grouped_matmul.fetches)
        ahead = three_products(grouped_matmul.fetches_ahead)
    seen = jnp.concatenate([seen, apart if decode else apart * 0,
                            jnp.stack([visited, dead, reads, ahead])])
    return {"routing": cache["routing"] + seen.astype(jnp.uint32)}


# Positions a step program attends over at a time.  A module constant,
# not a parameter: it decides how a row's float32 sums are grouped, and
# that grouping has to be the same in every program and under every
# bound (``_attend_slab``).  Settled on a v5e: 256 gave 1-3 % more
# tokens a second than 512 in every serving cell (the walk's bound
# follows the longest row more closely), PERF.md section 6, PR 33.
ATTEND_BLOCK = 256


def _span_blocks(longest, max_seq: int):
    """Blocks of ``ATTEND_BLOCK`` positions that hold positions
    0..``longest`` - 1 (a traced scalar) of a ``max_seq``-position slab:
    at least one, at most the slab's own."""
    size = min(ATTEND_BLOCK, max_seq)
    return jnp.clip((longest + size - 1) // size, 1, -(-max_seq // size))


def span_positions(longest: int, max_seq: int) -> int:
    """The positions ``_span_blocks`` makes a step walk, on the host."""
    size = min(ATTEND_BLOCK, max_seq)
    return min(max(-(-longest // size), 1) * size, max_seq)


def _decode_kernel(c: LlamaConfig, mesh, max_seq: int) -> bool:
    """Whether a decode step's rows attend over a layer's slabs of
    ``max_seq`` positions — and over a window layer's rings, whose rows
    have the slabs' shape a position (PR 59: the same kernel with the
    ring's mask) — through ``ops/pallas/decode_attention.py`` (each
    ACTIVE row's own blocks, read where they lie) and not through
    ``_attend_slab``'s XLA walk.  The walk keeps slabs sharded over a
    ``mesh`` (a Mosaic kernel is not partitioned automatically), any
    backend but the TPU, and a head that is no whole lane tiles (the
    kernel's blocks are cut by the 128 lanes) — of latent slabs the
    latent, and the slab's own length too: the chip holds the rotary
    keys with the positions ALONG the lanes, where the kernel takes its
    blocks of them."""
    if mesh is not None or jax.default_backend() != "tpu":
        return False
    if c.kv_lora_rank:
        return c.kv_lora_rank % 128 == 0 and max_seq % 128 == 0
    return c.head_dim % 128 == 0


def read_positions(contexts, max_seq: int) -> int:
    """The positions the kernel reads for a decode step whose active
    rows hold ``contexts`` positions each, on the host: every row's own
    whole blocks (``decode_attention.blocks_read``)."""
    return sum(span_positions(n, max_seq) for n in contexts)


def _ring_holds(top, rows, ring: int):
    """The position each of a ring's ``rows`` holds once position
    ``top`` is written: the newest p <= top with p = row (mod ring);
    negative where the row holds none yet."""
    return top - (top - rows) % ring


def _rows_of(pos, live, length: int, max_seq: int):
    """Where positions ``pos`` are written in a slab of ``length`` rows
    of a ``max_seq``-position cache — where they lie, or in a ring at
    ``pos mod length``; a row not ``live`` (padding, an inactive or a
    full slot) out of bounds, so that the scatter drops it."""
    if length != max_seq:
        pos, live = pos % length, live & (pos < max_seq)
    return jnp.where(live, pos, jnp.int32(length))


def _attend_slab(xq, ks, vs, i, slot, pos, blocks, c: LlamaConfig,
                 w_kvb=None, window: int = 0, top=None, visits=None):
    """Attention of rows ``xq`` (rows, heads, hd), row ``r`` over cached
    positions 0..``pos[r]`` of layer ``i`` of the CARRIED slabs ``ks``,
    ``vs`` (layers, slots, max_seq, *position): of ONE slot's slab that
    the rows share (``slot`` a scalar: a chunk's), or row ``r`` of slot
    ``r``'s (``slot`` None: a decode step's).

    Which rows take which path: a decode step's rows that bring their
    step's ``visits`` (``decode_attention.work_list``, which
    ``_decode_rows`` builds once a step on one TPU device, over the
    slabs and over a window layer's rings: ``_decode_kernel``) go
    through ``ops/pallas/decode_attention.py`` — the same blocks, sums
    and roundings as stated below, each ACTIVE row's own blocks alone
    fetched from the carried slabs where they lie, nothing for an idle
    slot, whose output is zeros; latent slabs too (PR 57), between
    ``w_kvb``'s two by-head products, which stay here; a window layer's
    rings too (PR 59), under the ring's mask as stated below.  A chunk's
    rows (512 of them share one slot's blocks: matrix-shaped already),
    slabs under a mesh and every other backend take the XLA walk that
    follows.  The engine counts both a decode step
    (``LLMEngine.stats``): ``decode_walk_positions``, what the walk
    reads of a layer's slabs — every slot as far as the longest active
    row — and ``decode_read_positions``, what the path taken reads
    (``read_positions`` for the kernel); ``window_walk_positions`` and
    ``window_read_positions`` the same of the window layers' rings.

    The slab is walked in blocks of ``ATTEND_BLOCK`` positions, each
    sliced out of the carried array where it lies, the first ``blocks``
    of them (a traced scalar, ``_span_blocks``: the reserve behind the
    longest live position is never read), with the online softmax's
    running maximum, denominator and output in float32.  A row's sums
    run over blocks 0, 1, 2, … in that order whatever ``blocks`` is, and
    a block wholly behind ``pos[r]`` adds exact zeros under a rescale of
    exactly 1: a row's output does not depend, to the bit, on how far
    the OTHER rows made the walk go.  A row whose position lies behind
    the walk (an inactive slot's, a chunk's padding) attends over what
    was walked; nobody reads it.  Every live row sees its own position,
    so no denominator is zero.  A slab no longer than a
    block is one block; the last block of one that is not a multiple
    starts early and masks what the block before it covered.

    With ``window`` the slabs are a window layer's RINGS
    (``ring_positions``) and ``top`` is the newest position written —
    the slot's (a scalar) or each row's own slot's (rows,).  The walk is
    the same, over the ring's rows in the order they lie; a row's mask
    is made from the position each ring row holds NOW (``_ring_holds``):
    row ``r`` at position ``t`` sees a ring row iff it holds a position
    ``s`` with 0 <= t - s < window.  A block may then be masked whole
    before a row has seen anything, so the running maximum starts at
    float32's lowest finite value, not at -inf: such a block adds exact
    zeros under a rescale of exactly 1 there too, and the first score
    seen takes the maximum over with a rescale of exactly 0 — the order
    of a row's sums still depends on its own position alone.

    Grouped-query slabs hold keys and values per KV head: bf16 inputs
    with fp32 accumulation keep the products at full MXU rate without an
    fp32 copy of a block (see ops/attention).  With ``w_kvb`` the slabs
    are latent ones, ``c_kv`` (rank) and ``k_rope`` (rope) a position
    and no heads axis, attended in the ABSORBED form: ``w_kvb``'s keys'
    part goes into the query and its values' part onto the output,

        score_h(s) = (q_nope_h W_K,h^T) · c_kv(s) + q_rope_h · k_rope(s)
        out_h      = (sum_s p_h(s) c_kv(s)) W_V,h

    the same values as making every cached position's keys and values
    (c_kv(s) W_K,h, c_kv(s) W_V,h) first, without ever holding them:
    per position a head then multiplies rank + rope and rank values
    against the slab's one row instead of reading nope + rope and v of
    its own.  A chunk runs it too: making the keys and values of the
    positions it attends over costs positions * rank * heads * (nope +
    v) multiply-adds, which the narrower per-head products win back only
    beyond some 170 rows a call."""
    rows, max_seq = xq.shape[0], ks.shape[2]
    size = min(ATTEND_BLOCK, max_seq)
    every = slot is None                         # row r reads slot r
    through = every and visits is not None

    def kernel(q, scale):
        return decode_attention.decode_attention(
            q, ks, vs, i, pos, visits, block=ATTEND_BLOCK, scale=scale,
            window=window, interpret=jax.default_backend() != "tpu")

    t = "rt" if every else "t"                   # a block's leading axes
    f32 = {"preferred_element_type": jnp.float32}
    if w_kvb is None:
        if through:
            return kernel(xq, c.attention_multiplier or c.head_dim ** -0.5)
        # the query heads of a KV head, from the queries themselves: a
        # block step's rows bring a slot's places folded among them
        q = xq.reshape(rows, c.n_kv_heads, xq.shape[1] // c.n_kv_heads,
                       c.head_dim)
        scale = c.attention_multiplier or 1 / jnp.sqrt(
            jnp.float32(c.head_dim))
        heads, width = q.shape[1:3], c.head_dim

        def scores(bk, bv):
            return jnp.einsum(f"rkgd,{t}kd->rkgt", q, bk, **f32)

        def values(probs, bk, bv):
            return jnp.einsum(f"rkgt,{t}kd->rkgd", probs, bv, **f32)
    else:
        nope = c.qk_nope_head_dim
        wk, wv = _kvb_by_head(w_kvb, c)
        q_lat = jnp.einsum("rhd,chd->rhc", xq[..., :nope], wk,
                           **f32).astype(xq.dtype)
        q_rope, scale = xq[..., nope:], c.attn_scale

        def by_head(out):                        # the latents' sums -> v
            return jnp.einsum("rhc,chd->rhd", out.astype(xq.dtype), wv,
                              **f32).astype(xq.dtype)

        if through:
            return by_head(kernel((q_lat, q_rope), scale))
        heads, width = (c.n_heads,), c.kv_lora_rank

        def scores(bk, bv):                      # bk: c_kv, bv: k_rope
            return (jnp.einsum(f"rhc,{t}c->rht", q_lat, bk, **f32)
                    + jnp.einsum(f"rhc,{t}c->rht", q_rope, bv, **f32))

        def values(probs, bk, bv):
            return jnp.einsum(f"rht,{t}c->rhc", probs, bk, **f32)

    def block(slabs, start):
        at = (i, 0 if every else slot, start) + (0,) * (slabs.ndim - 3)
        shape = (1, slabs.shape[1] if every else 1, size) + slabs.shape[3:]
        taken = lax.dynamic_slice(slabs, at, shape)
        taken = taken[0] if every else taken[0, 0]
        if w_kvb is None and c.flat_kv_heads:    # held side by side
            taken = taken.reshape(*taken.shape[:-1], c.n_kv_heads, -1)
        return taken

    def walk(b, state):
        high, denom, out = state
        first = b * size
        start = jnp.minimum(first, max_seq - size)
        bk, bv = block(ks, start), block(vs, start)
        at = start + jnp.arange(size)
        if window:
            held = _ring_holds(jnp.reshape(top, (-1, 1)), at, max_seq)
            valid = (at >= first) & (held >= 0) & (held <= pos[:, None]) & (
                pos[:, None] - held < window)
        else:
            valid = (at >= first) & (at <= pos[:, None])      # (rows, size)
        s = jnp.where(valid.reshape(rows, *(1,) * len(heads), size),
                      scores(bk, bv) * scale, -jnp.inf)
        new_high = jnp.maximum(high, jnp.max(s, axis=-1))
        keep = jnp.exp(high - new_high)
        probs = jnp.exp(s - new_high[..., None])
        denom = keep * denom + jnp.sum(probs, axis=-1)
        out = keep[..., None] * out + values(probs.astype(ks.dtype), bk, bv)
        return new_high, denom, out

    _, denom, out = lax.fori_loop(0, blocks, walk, (
        jnp.full((rows, *heads), jnp.finfo(jnp.float32).min, jnp.float32),
        jnp.zeros((rows, *heads), jnp.float32),
        jnp.zeros((rows, *heads, width), jnp.float32)))
    out = out / denom[..., None]
    if w_kvb is None:
        return out.reshape(xq.shape).astype(xq.dtype)
    return by_head(out)


def _as_held(x, slab):
    """A call's new rows ``x`` (rows, *position) as ``slab`` holds a
    position (``kv_slabs``)."""
    return x.astype(slab.dtype).reshape(x.shape[0], *slab.shape[3:])


def _slab_positions(cache: dict, c: LlamaConfig) -> int:
    """A slot's ``max_seq``, as the cache was made."""
    return cache[next(iter(kv_slabs(c)))].shape[2]


def _pass_first(c: LlamaConfig, u):
    """The first slab layer of a looped model's pass ``u`` (traced):
    the (pass, layer) index's pass part."""
    return u * c.n_layers


def _scan_layers(params: dict, x, cache: dict, c: LlamaConfig, positions,
                 write_attend, write_state=None, live=None, *, decode: bool,
                 mesh=None, counted=None):
    """A step program's layers over rows ``x`` (rows, dim): a
    ``lax.scan`` over each stack's PERIODS of the layer pattern
    (``_stacks``, ``LlamaConfig.period``), whose body is the period's
    layers — unlike ones, each ``apply_block`` with its own way to
    attend; a period of one where all layers are alike — and whose
    carry is the rows and the whole cache.  Returns (x, the cache's new
    entries: its slabs, its counters — a ``decode`` step's counted
    apart as well, ``ROUTING_COUNTERS``).  ``mesh``: the one the
    parameters are sharded over, if any (``_grouped_tile``).  ``live``
    ((rows,) bool, as ``_row_groups`` joins it; None: every row) is
    made once a program and goes to every layer's routed experts, which
    a row that is not live does not reach (``_routed_mlp``); a dense
    layer's ``_mlp`` drops it.

    A looped model (``LlamaConfig.loops``) runs that scan ``loops``
    times, as a ``lax.scan`` over the passes AROUND it — one loop in
    the program, the same weights every pass, rows and cache its carry
    too: pass ``u``, layer ``l`` writes and reads slab layer ``u *
    n_layers + l``.  The final norm closes every pass, so the rows
    returned are NORMED (``_logits`` does not norm them again), and the
    exit gate reads each pass's normed rows: ``counted`` ((rows,) bool:
    a decode step's active rows, None where the program has none) says
    whose exit distribution goes into ``cache["exits"]``
    (``EXIT_COUNTERS``) — fewer than ``live``: a riding chunk's
    tokens are live and are no decode rows.

    ``write_attend(ks, vs, i, window, xq, xk, xv[, w_kvb]) -> (out, (ks,
    vs))`` is the block's attention over the carried slabs of the
    layer's kind (``kv_slabs``: keys and values, or the latent and the
    rotary key; a window layer's the rings, ``window`` then its width,
    else 0) and has one order: the cache travels as the loop's CARRY,
    which the compiler aliases to the donated input, so layer ``i``'s
    new rows are written where they lie (a row whose position is
    max_seq is dropped by the scatter), and only THEN does
    ``_attend_slab`` read the carried array, block by block and no
    further than the step's longest live position — no layer's slab is
    ever sliced out whole.  As a scanned input and output of the loop
    the slabs are copied about three times a call; attending over the
    old slab with the new rows beside it compiles to more temporaries
    and reorders the float32 sums.

    ``write_state(held, i, *inputs) -> (out, held)`` is a
    RECURRENT layer's, ``inputs`` what ``apply_block`` hands the kind's
    ``attend``: it runs the call's rows from the carried states of
    recurrent layer ``i`` (``held``: the cache's ``state_slabs`` by
    name — ``s`` and ``conv``, or ``conv`` alone) and leaves the new
    ones where they lay; a model without such layers never calls it.
    Slab layers and state layers count through the runs, a routed
    model's leading dense layers first."""
    # as far as a row's position goes: a full slot's is max_seq itself,
    # a chunk's last padded row's max_seq + chunk - 2
    cos, sin = _rope_tables(c, _slab_positions(cache, c) + x.shape[0])
    names = tuple(kv_slabs(c))
    states = tuple(state_slabs(c))
    tile = _grouped_tile(c, x.shape[0], mesh)

    def scan_stack(carry, stacks, cfg, first):
        """``first``: the places in the cache of the run's first slab
        layer and of its first state layer."""
        hoisted = {name: _hoist_experts(stack, cfg, name)
                   for name, stack in stacks.items()}
        experts = {name: parts[1] for name, parts in hoisted.items()}
        layers, whole = _by_period(
            {name: parts[0] for name, parts in hoisted.items()}, cfg)
        kinds = cfg.kinds

        def period(carry, scanned):
            x, slabs = carry                     # slabs: the whole cache
            layers, p = scanned                  # p: the period's number
            loads = []                           # in its run
            for j, kind in enumerate(kinds):
                stack, a_period, rank = cfg.place(j)
                # the layer's place among the slabs of its kind
                i = (p * kinds.count(kind) + kinds[:j].count(kind)
                     + (first[0] if kind == "full" else
                        first[1] if kind in RECURRENT else 0))
                if kind in RECURRENT:
                    held = states
                    attend = functools.partial(
                        write_state, {name: slabs[name] for name in held},
                        i)
                else:
                    held = names[2:] if kind == "window" else names[:2]
                    attend = functools.partial(
                        write_attend, *(slabs[name] for name in held), i,
                        cfg.window if kind == "window" else 0)
                x, kept, load = apply_block(
                    {**_place(layers, whole, j, cfg), **experts[stack]}, x,
                    cfg, cos, sin, positions, attend, _unconstrained,
                    p * a_period + rank, kind, tile, live)
                slabs = {**slabs, **(kept if kind in RECURRENT
                                     else dict(zip(held, kept)))}
                loads.append(load)
            return (x, slabs), (loads[0] if len(loads) == 1
                                or loads[0] is None else jnp.stack(loads))

        carry, loads = lax.scan(
            period, carry, (layers, next(iter(hoisted.values()))[2]))
        return carry, _unperiod(loads, cfg)

    def once(carry, first):
        """Every stack, once; ``first``: the pass's first slab layer."""
        loads, first = None, (first, 0)
        for stacks, cfg in _stacks(params, c):
            carry, loads = scan_stack(carry, stacks, cfg, first)
            first = (first[0] + cfg.layer_counts()[1],
                     first[1] + cfg.n_recurrent)
        return carry, loads

    def a_pass(carry, u):
        with jax.named_scope("loop_pass"):
            (x, slabs), _ = once(carry, _pass_first(c, u))
            x = _norm(x, params["norm_f"], c)
        if not c.exit_gate:
            return (x, slabs), None
        with jax.named_scope("exit_gate"):
            gate = params["exit_gate"]
            return (x, slabs), jax.nn.sigmoid(jnp.dot(
                x, gate["w"], preferred_element_type=jnp.float32)[:, 0]
                + gate["b"].astype(jnp.float32))

    carry, gates = (x, {n: cache[n] for n in names + states}), None
    if c.loops == 1:
        carry, loads = once(carry, 0)
    else:
        carry, gates = lax.scan(a_pass, carry, jnp.arange(c.loops))
        loads = None
    routed = dead = None
    if loads is not None:
        # (row, expert) pairs of every layer's router: the live rows'
        # and the others', which reached no expert
        pairs = loads.shape[0] * c.experts_per_token
        rows = x.shape[0] if live is None else jnp.sum(live)
        routed, dead = pairs * rows, pairs * (x.shape[0] - rows)
    return carry[0], {**carry[1],
                      **_count_routing(
                          cache, loads, routed, decode, tile, dead,
                          (c.dim, c.mlp_dim, jnp.dtype(c.dtype).itemsize)),
                      **_count_exits(cache, gates, counted)}


def _chunk_rows(cache: dict, c: LlamaConfig, chunk: int, slot, start,
                chunk_len):
    """What the ``chunk`` rows of ONE prompt in ``slot`` — ``chunk_len``
    real tokens from absolute position ``start`` on, the rest padding —
    do with a layer, for ``_row_groups``: ``(rows, their positions,
    which are live, write, attend, state)``; live are the real tokens.
    Under ``block_length`` a row sees its
    whole block, as far as the chunk's own end: the engine's chunks
    hold whole blocks from a block's first place on."""
    max_seq = _slab_positions(cache, c)
    offs = jnp.arange(chunk, dtype=jnp.int32)
    pos = start + offs                           # (chunk,) absolute
    # Pad tokens' writes land out of bounds (``_rows_of``) → dropped by
    # the scatter; their values never reach the slab or the masked
    # attention.
    top = start + chunk_len - 1                  # the newest position
    # the last position a row sees: its own, or its block's last
    seen = pos if not c.block_length else jnp.minimum(
        pos // c.block_length * c.block_length + c.block_length - 1, top)
    # by the rows of a slab: max_seq, and a window layer's ring
    lengths = {cache[name].shape[2] for name in kv_slabs(c)}
    write_pos = {n: _rows_of(pos, offs < chunk_len, n, max_seq)
                 for n in lengths}
    blocks = {n: _span_blocks(top + 1, n) for n in lengths}

    def write(ks, vs, i, xk, xv):
        """The chunk's real rows into (layer i, slot)."""
        n = ks.shape[2]
        return (ks.at[i, slot, write_pos[n]].set(_as_held(xk, ks)),
                vs.at[i, slot, write_pos[n]].set(_as_held(xv, vs)))

    def attend(ks, vs, i, window, xq, w_kvb=None):
        """Over that slot's slab — a window layer's ring — causally by
        absolute position, as far as the chunk's own end."""
        return _attend_slab(xq, ks, vs, i, slot, seen, blocks[ks.shape[2]],
                            c, w_kvb, window, top)

    def state(held, i, u, *inputs):
        """A recurrent layer: the chunk from (layer i, slot)'s state —
        from an EMPTY one where the prompt begins there (``start`` 0:
        what the slot's last occupant left is never read) — and back
        goes the state behind the chunk's last REAL token: padding
        neither decays nor writes, and the convolution's tail is the
        last real inputs — of a chunk with fewer real tokens than the
        tail is long, the tail it began from moved up by them.  A conv
        layer keeps the tail alone."""
        real = offs < chunk_len
        s, conv = held.get("s"), held["conv"]
        if s is not None:
            s0 = jnp.where(start == 0, 0.0, s[i, slot])
        tail = jnp.where(start == 0, 0, conv[i, slot]).astype(conv.dtype)
        u = u.astype(conv.dtype)
        if c.recurrent == "conv":
            out, ext = delta_rule.causal_conv(u, tail, *inputs)
        elif c.recurrent == "ssm":
            dt, weights = inputs
            y, ext = delta_rule.causal_conv(
                u, tail, weights["conv_w"], weights["conv_b"])
            out, s1 = ssd.chunk_ssd(
                *_ssm_run(y, jnp.where(real[:, None], dt, 0.0), weights, c),
                s0, operand=c.dtype)
        else:
            g, beta, conv_w = inputs
            y, ext = delta_rule.causal_conv(u, tail, conv_w)
            out, s1 = _delta_forms(c)[0](
                *_linear_qkv(y, c),
                jnp.where(jnp.expand_dims(real, range(1, g.ndim)), g, 0.0),
                jnp.where(real[:, None], beta, 0.0), s0)
        tail = lax.dynamic_slice_in_dim(ext, chunk_len, tail.shape[0])
        return out, {**({} if s is None else {"s": s.at[i, slot].set(s1)}),
                     "conv": conv.at[i, slot].set(tail)}

    return chunk, pos, offs < chunk_len, write, attend, state


def _decode_rows(cache: dict, c: LlamaConfig, active, mesh=None):
    """What a decode step's rows — one a slot, those ``active`` live —
    do with a layer, for ``_row_groups``: ``(rows, their positions,
    which are live, write, attend, state)``.  ``mesh``: the one the
    slabs are sharded over, if any (``_decode_kernel``)."""
    max_seq = _slab_positions(cache, c)
    pos = cache["length"]                       # (slots,) write position
    # The walk ends behind the longest ACTIVE row: an idle slot that
    # holds a resident session's long slab does not lengthen it.
    longest = jnp.max(jnp.where(active, pos, 0)) + 1
    slots = jnp.arange(pos.shape[0])
    # by the rows of a slab (max_seq, and a window layer's ring).
    # Inactive slots' scatter writes are pushed out of bounds (and
    # dropped), as a full slot's are; their lengths hold still.
    lengths = {cache[name].shape[2] for name in kv_slabs(c)}
    write_pos = {n: _rows_of(pos, active, n, max_seq) for n in lengths}
    blocks = {n: _span_blocks(longest, n) for n in lengths}
    # each active row's own blocks, for the kernel: one list a step for
    # the slabs and one for a window layer's rings, the layers' alike
    visits = {n: decode_attention.work_list(pos, active, ATTEND_BLOCK, n)
              for n in lengths} if _decode_kernel(c, mesh, max_seq) else None

    def write(ks, vs, i, xk, xv):
        """One row a slot into layer i."""
        n = ks.shape[2]
        return (ks.at[i, slots, write_pos[n]].set(_as_held(xk, ks)),
                vs.at[i, slots, write_pos[n]].set(_as_held(xv, vs)))

    def attend(ks, vs, i, window, xq, w_kvb=None):
        """Over the layer's slabs (a window layer's rings), each slot up
        to its own position, with the visits of the slab's length."""
        n = ks.shape[2]
        return _attend_slab(xq, ks, vs, i, None, pos, blocks[n], c, w_kvb,
                            window, pos, visits[n] if visits else None)

    def state(held, i, u, *inputs):
        """A recurrent layer: one token a slot from layer i's states; a
        slot that is not ``active`` — free, or between two chunks of
        its own prompt — keeps its state and its tail bit for bit.  A
        conv layer keeps the tail alone."""
        s, conv = held.get("s"), held["conv"]
        if c.recurrent == "conv":
            out, tail = delta_rule.causal_conv_step(u, conv[i], *inputs)
        elif c.recurrent == "ssm":
            dt, weights = inputs
            y, tail = delta_rule.causal_conv_step(
                u, conv[i], weights["conv_w"], weights["conv_b"])
            out, new = ssd.ssd_step(*_ssm_run(y, dt, weights, c), s[i],
                                    active)
        else:
            g, beta, conv_w = inputs
            y, tail = delta_rule.causal_conv_step(u, conv[i], conv_w)
            out, new = _delta_forms(c)[1](
                *_linear_qkv(y, c), g, beta, s[i], active)
        tail = jnp.where(active[:, None, None], tail, conv[i])
        return out, {
            **({} if s is None else {
                "s": lax.dynamic_update_index_in_dim(s, new, i, 0)}),
            "conv": lax.dynamic_update_index_in_dim(conv, tail, i, 0)}

    return pos.shape[0], pos, active, write, attend, state


def _block_rows(cache: dict, c: LlamaConfig, live, first, mesh=None):
    """What ``block_length`` rows a slot — slot ``r``'s block at
    positions ``first[r]`` .. ``first[r] + block_length - 1``,
    slot-major, those of ``live`` slots live — do with a layer, for
    ``_row_groups``: ``(rows, their positions, which are live, write,
    attend, state)``.
    Written then attended, as every group: a place's keys and values lie
    BEHIND the slot's length until a step moves it over them
    (``_step_lengths``), and the next step of a block in flight writes
    them again.  Every place of a block sees the positions before it and
    all of its block, so a slot's places ask ONE question of the slab
    and go through it together: folded among the query heads of their
    KV head — (slots, kv_heads, places x heads a KV head) — they are a
    decode step's rows with ``block_length`` times the heads, to the
    XLA walk and to ``ops/pallas/decode_attention.py`` alike
    (``_decode_kernel``), and a slot's blocks are read once for all its
    places."""
    size, max_seq = c.block_length, _slab_positions(cache, c)
    n_slots = first.shape[0]
    slots = jnp.repeat(jnp.arange(n_slots), size)
    pos = (first[:, None] + jnp.arange(size, dtype=jnp.int32)).reshape(-1)
    # a place behind the slab's end is dropped by the scatter, an idle
    # slot's pushed there (``_rows_of``)
    live_rows = jnp.repeat(live, size)
    write_pos = _rows_of(pos, live_rows, max_seq, max_seq)
    seen = first + size - 1                      # (slots,) a block's last
    blocks = _span_blocks(jnp.max(jnp.where(live, seen, 0)) + 1, max_seq)
    visits = decode_attention.work_list(
        seen, live, ATTEND_BLOCK, max_seq) if _decode_kernel(
            c, mesh, max_seq) else None
    group = c.n_heads // c.n_kv_heads

    def write(ks, vs, i, xk, xv):
        return (ks.at[i, slots, write_pos].set(_as_held(xk, ks)),
                vs.at[i, slots, write_pos].set(_as_held(xv, vs)))

    def attend(ks, vs, i, window, xq, w_kvb=None):
        with jax.named_scope("block_rows"):
            folded = xq.reshape(n_slots, size, c.n_kv_heads, group,
                                c.head_dim).transpose(0, 2, 1, 3, 4)
            out = _attend_slab(
                folded.reshape(n_slots, -1, c.head_dim), ks, vs, i, None,
                seen, blocks, c, visits=visits)
            return out.reshape(n_slots, c.n_kv_heads, size, group,
                               c.head_dim).transpose(0, 2, 1, 3, 4).reshape(
                xq.shape)

    return n_slots * size, pos, live_rows, write, attend, None


def _row_groups(*groups):
    """``_scan_layers``' ``(positions, write_attend, write_state,
    live)`` for a step program whose rows are ``groups`` laid end to end
    (``_decode_rows``, ``_chunk_rows``): a layer's rows are split where
    the groups meet, EVERY group writes its rows into the carried slabs
    first, then each attends over them as it does alone, one group
    after the other, and the outputs are joined again — the groups'
    slots are disjoint, so the writes do not meet and a group reads
    what it would read alone.  A recurrent layer's states go through
    the groups in turn.  One group is the group itself: nothing is split or
    joined.  The first group is the decode step's — one row a slot, or
    of a model that generates by diffusion over blocks ``block_length``
    rows a slot (``_block_rows``: a BLOCK step, whose rows bring no
    token each but a block's places, 0 to all of which the step's
    transfer rule fills; behind them, where the caller has blocks
    CLOSING, as many rows a slot again: ``_step_rows``) — and a chunk's
    rows may ride behind it.

    ``live`` ((rows,) bool) joins what each group knows of its rows:
    an active slot's, a chunk's real tokens, a block of a live slot —
    the closing rows' only where the slot has a block closing.  A row
    that is not live writes nothing (each group pushes its write out of
    bounds) and nobody reads what it becomes; it is computed all the
    same, the shapes being static, except where its cost follows the
    rows: the routed experts (``_routed_mlp``)."""
    edges = [0]
    for rows, *_ in groups:
        edges.append(edges[-1] + rows)

    def split(x):
        if len(groups) == 1:
            return [x]
        return [x[a:b] for a, b in zip(edges, edges[1:])]

    def join(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def write_attend(ks, vs, i, window, xq, xk, xv, w_kvb=None):
        for (*_, write, _, _), k, v in zip(groups, split(xk), split(xv)):
            ks, vs = write(ks, vs, i, k, v)
        outs = []
        for (*_, attend, _), q in zip(groups, split(xq)):
            if outs:
                # One walk hands the slabs to the next: as two readers
                # of one array the TPU compiler gave both walks a copy
                # of the WHOLE slabs in the products' layout, a layer —
                # 1 GiB where a block at a time is re-laid
                # (``tests/test_tpu_compile.py`` holds that no program
                # moves a slab).  The barrier computes nothing.
                outs[-1], ks, vs = lax.optimization_barrier(
                    (outs[-1], ks, vs))
            outs.append(attend(ks, vs, i, window, q, w_kvb))
        return join(outs), (ks, vs)

    def write_state(held, i, *inputs):
        # the last input is the layer's own leaves, the others are rows
        outs = []
        for (*_, state), *mine in zip(groups, *map(split, inputs[:-1])):
            out, held = state(held, i, *mine, inputs[-1])
            outs.append(out)
        return join(outs), held

    return (join([pos for _, pos, *_ in groups]), write_attend,
            write_state, join([live for _, _, live, *_ in groups]))


def _embed(params: dict, tokens, c: LlamaConfig):
    """Token ids -> their rows of the embedding, times the config's
    ``embedding_multiplier``."""
    x = params["embed"][tokens].astype(c.dtype)
    if c.embedding_multiplier != 1.0:
        x = x * c.embedding_multiplier
    if c.hc_mult > 1:
        # every residual stream starts as the token's embedding
        x = jnp.broadcast_to(x[..., None, :],
                             (*x.shape[:-1], c.hc_mult, c.dim))
    return x


def _joined(x, c: LlamaConfig):
    """The residual state behind the last layer as ONE vector a token:
    several streams (``hc_mult``) summed, in float32, rounded once."""
    if c.hc_mult == 1:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=-2).astype(x.dtype)


def _head(params: dict, x, c: LlamaConfig):
    """Normed rows -> their logits, float32, over the config's
    ``logits_scaling``."""
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(c.dtype)).astype(jnp.float32)
    return logits if c.logits_scaling == 1.0 else logits / c.logits_scaling


def _closed(params: dict, x, c: LlamaConfig):
    """Rows behind the layers (``_scan_layers``), normed for the head:
    a looped model's every pass ended in the norm, the last too."""
    return x if c.loops > 1 else _norm(_joined(x, c), params["norm_f"], c)


def _logits(params: dict, x, c: LlamaConfig):
    """Rows behind the layers -> their logits."""
    return _head(params, _closed(params, x, c), c)


def _chunk_logits(params: dict, x, chunk_len, c: LlamaConfig):
    """A chunk's rows behind the layers -> the logits (vocab,) at its
    last REAL token: the rows normed, that one's multiplied with the
    head as one vector."""
    return _head(params, jnp.take(_closed(params, x, c),
                                  jnp.maximum(chunk_len - 1, 0), axis=0), c)


def prefill_chunk_into_cache(params: dict, tokens, cache: dict, slot,
                             start, chunk_len, config: LlamaConfig, *,
                             mesh=None):
    """Ingest ONE fixed-size chunk of a prompt into ``slot``.

    tokens: (chunk,) int32 — ``chunk_len`` real tokens, zero-padded to
    the engine's fixed chunk width.  ``slot``, ``start`` (absolute
    offset of the chunk in the slab) and ``chunk_len`` are all traced
    scalars, so a single compiled variant covers every chunk of every
    prompt, wherever in the slab it lies.

    Chunk queries attend against the slot's slab as far as the chunk's
    own end, ``start + chunk_len`` rounded up to a block
    (``_attend_slab``: earlier chunks' rows plus this chunk's own,
    causally masked) — the same walk as `decode_step`'s, so the
    dense-slab static-shape discipline holds and the reserve behind the
    prompt is not read.  Pad positions write nothing: their scatter
    indices are pushed out of bounds and dropped, and the returned
    logits are taken at the chunk's last REAL token.

    Returns (logits (vocab,) fp32, new cache with slot length set to
    ``start + chunk_len``).  ``mesh``: the one the parameters are
    sharded over, if any (``_grouped_tile``).
    """
    c = config
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    x = _embed(params, tokens, c)                # (chunk, dim)
    x, written = _scan_layers(
        params, x, cache, c, *_row_groups(_chunk_rows(
            cache, c, tokens.shape[0], slot, start, chunk_len)),
        decode=False, mesh=mesh)
    cache = {**written,
             "length": cache["length"].at[slot].set(start + chunk_len)}
    return _chunk_logits(params, x, chunk_len, c), cache


def _stepped(length, active, max_seq: int, by: int = 1):
    """A decode step's new lengths: + 1 where ``active`` (a block
    step's: + ``by``, a block, where it stores), clamped so a full slot
    never indexes past its slab."""
    return jnp.where(active, jnp.minimum(length + by, jnp.int32(max_seq)),
                     length)


def _step_rows(cache: dict, c: LlamaConfig, active, mesh, closing=None):
    """A decode step's row groups, for ``_row_groups``: one row a slot —
    or, of a model that generates by diffusion over blocks,
    ``block_length`` rows a slot (``_block_rows``), the block in flight
    at the slot's length.  With ``closing`` ((slots,) bool: the slots
    whose last block's FINAL tokens ride this step, ``decode_step``)
    there are two such groups: the blocks in flight, one block further
    on where the slot has a block closing, and behind them the closing
    blocks at the lengths themselves.  The order of ``_row_groups``
    gives the mask: both are written, then the closing rows see the
    stored positions and their own block (nothing of the new one, which
    lies behind their last place), and the new block's rows the stored
    positions, the closing block as this step wrote it and all of their
    own — position ``t`` sees ``s`` iff ``s // block_length <= t //
    block_length``."""
    if not c.block_length:
        return [_decode_rows(cache, c, active, mesh)]
    length = cache["length"]
    if closing is None:
        return [_block_rows(cache, c, active, length, mesh)]
    return [_block_rows(cache, c, active,
                        length + c.block_length * closing, mesh),
            _block_rows(cache, c, active & closing, length, mesh)]


def _step_lengths(cache: dict, c: LlamaConfig, active, store, closing=None):
    """The lengths behind a decode step: every ``active`` slot one
    further — or, of a block step, the slots that ``store`` the block in
    flight or have a block ``closing`` a whole block further: ONE block
    a step (a slot with a block closing and no mask left in flight has
    nothing in flight: the slab's end)."""
    max_seq = _slab_positions(cache, c)
    if not c.block_length:
        return _stepped(cache["length"], active, max_seq)
    if closing is not None:
        store = store | closing
    return _stepped(cache["length"], active & store, max_seq,
                    c.block_length)


def _step_tokens(last_tokens, closing, *more):
    """The tokens of a step's rows in the order of its groups
    (``_step_rows``): the decode rows', a block step's closing blocks'
    behind them, then ``more`` (a riding chunk's)."""
    parts = [last_tokens.reshape(-1)]
    if closing is not None:
        parts.append(closing[0].reshape(-1))
    parts += more
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def decode_step(params: dict, last_tokens, cache: dict,
                config: LlamaConfig, active, *, mesh=None, store=None,
                closing=None):
    """One token for every slot, attending against the cache.

    last_tokens: (slots,) int32 — the most recent token per slot.
    ``active`` ((slots,) bool): slots marked False neither write their
    row nor advance their length — an idle slot can hold a RESIDENT
    session's slab, which must stay bit-exact while the slot sits out
    decode steps.
    Returns (logits (slots, vocab) fp32, new cache with +1 lengths).
    ``mesh``: the one the parameters are sharded over, if any
    (``_grouped_tile``).

    Of a model that generates by diffusion over blocks
    (``LlamaConfig.block_length``) it is a BLOCK step: ``last_tokens``
    (slots, block_length) is every slot's block in flight as it is FED
    — decided places their token, the others ``mask_token`` — at
    positions ``length`` .. ``length + block_length - 1``; every place
    attends the stored positions and all places of its block
    (``_block_rows``), and the logits (slots * block_length, vocab),
    slot-major, are each place's own token's.  The keys and values of
    the block are written behind the length either way; ``store``
    ((slots,) bool) says whose length moves over them, by a whole block
    — the store pass of a block with no mask left, decided by the
    caller from the slot's own masks.  A denoise step leaves ``length``
    as it is and its rows are overwritten by the block's next step.

    ``closing`` (``(tokens (slots, block_length), live (slots,) bool)``)
    lets a block's store pass RIDE the next block's first step: where
    ``live`` (and ``active``) the slot's last block, its final
    ``tokens``, goes through the layers at ``length`` .. ``length +
    block_length - 1`` in this step too, the block in flight lies one
    block further on and sees it (``_step_rows``), and the length moves
    over the closing block.  The closing rows' keys and values are what
    a store pass of their own writes; they have no logits: the rows
    normed and multiplied with the head are those of the blocks in
    flight alone."""
    c = config
    x = _embed(params, _step_tokens(last_tokens, closing), c)  # (rows, dim)
    live = None if closing is None else closing[1]
    x, written = _scan_layers(
        params, x, cache, c, *_row_groups(
            *_step_rows(cache, c, active, mesh, live)),
        decode=True, mesh=mesh, counted=active)
    if closing is not None:
        x = x[:last_tokens.size]
    return _logits(params, x, c), {
        **written, "length": _step_lengths(cache, c, active, store, live)}


def mixed_step(params: dict, last_tokens, tokens, cache: dict,
               config: LlamaConfig, active, slot, start, chunk_len, *,
               mesh=None, store=None, closing=None):
    """A decode step AND one prompt's chunk as one program: the
    ``slots`` decode rows (``decode_step``'s ``last_tokens`` and
    ``active``) followed by the chunk's rows
    (``prefill_chunk_into_cache``'s ``tokens``, ``slot``, ``start`` and
    ``chunk_len``) go through the layers together, ONE ``_scan_layers``
    pass over ``slots + chunk`` rows, so that norms, projections, the
    dense or the routed feed-forward (one top-k, one sort, one grouped
    product over the experts either hit, its row tile from the summed
    rows) and the shared experts read their weights once (the head
    twice: see below).
    Only the attention — and a linear layer's state — knows which rows
    it has (``_row_groups``): each part writes and attends exactly as
    its own program does.  The chunk's slot is not an ``active`` row
    (the engine's: a prompt decodes once it is ingested).

    Returns (the decode rows' logits (slots, vocab) fp32, the chunk's
    logits at its last real token (vocab,) fp32, the cache with
    ``length`` advanced for the active rows and set for the chunk's
    slot).  A routed model's counters count it as one execution among
    all, not among the decode steps (``ROUTING_COUNTERS``).  With no
    row active and ``chunk_len`` 0 at the slot's own length it leaves
    slabs, states and lengths as they are.  ``last_tokens`` (slots,
    block_length), ``store`` and ``closing``: a block step's, as
    ``decode_step`` takes them; the closing blocks' rows lie between
    the blocks in flight and the chunk.
    """
    c = config
    slots = last_tokens.size
    rows = slots if closing is None else slots + closing[0].size
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    x = _embed(params, _step_tokens(last_tokens, closing, tokens), c)
    live = None if closing is None else closing[1]
    x, written = _scan_layers(
        params, x, cache, c, *_row_groups(
            *_step_rows(cache, c, active, mesh, live),
            _chunk_rows(cache, c, tokens.shape[0], slot, start, chunk_len)),
        decode=False, mesh=mesh, counted=jnp.concatenate(
            [active, jnp.zeros(tokens.shape, bool)]) if c.exit_gate
        else None)
    # Behind the layers each part goes on as in its own program (a
    # looped model's rows were normed, row by row, as each pass ended) — the
    # decode rows normed and multiplied with the head together, the
    # chunk's rows normed and its last real token's multiplied as one
    # vector — and not the rows as one operand: on the chip the one-row
    # product differs from a row of a many-row product in every logit
    # (1.6e-3, PERF.md section 6, PR 39), and a token must not depend
    # on whether its prompt ended in company.
    length = _step_lengths(cache, c, active, store, live)
    return (_logits(params, x[:slots], c),
            _chunk_logits(params, x[rows:], chunk_len, c),
            {**written, "length": length.at[slot].set(start + chunk_len)})


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
