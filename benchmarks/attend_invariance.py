"""On the chip: a row's attention output, and a whole decode step's
logits for that row, are bit-equal whatever bound the OTHER rows'
lengths set for the walk over the cache (``llama._attend_slab``) — what
``tests/test_llama.py`` holds on the CPU at test size, here at Mistral's,
OLMoE's, A.X-K1's and Olmo Hybrid's widths and slabs (two layers each —
one period of Olmo Hybrid's four — random weights and cache); the same
of the rows' path through ``ops/pallas/decode_attention.py`` (PR 47:
what a decode step's rows take on the chip over every full slab, the
latent ones since PR 57 — a row alone, among rows of other lengths and
beside idle slots; the step's logits and the mixed step's decode rows go
through it too; PR 59: Command A+'s widths, one period of three
window layers to a full one at the cell's 16 x 32,768 — the RINGS
through the kernel under the ring's mask, row 0 before its ring has
wrapped and after, among rows that have and have not, beside idle slots
and alone, and how far the kernel's row lies from the walk's); and the mixed step's two walks (PR 39: ``_row_groups`` of a
decode step's rows and a chunk's) each give their rows what the same
walk gives them alone, to the bit, whatever the other part's lengths.
One JSON line a shape; through the chip tool, from the root:

    python -m benchmarks.attend_invariance [shape ...]
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ant_ray_tpu.models import llama
from ant_ray_tpu.ops.pallas import decode_attention
from ant_ray_tpu.ops.rope import YarnScaling

MISTRAL = llama.LlamaConfig(
    vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
    mlp_dim=14336, max_seq=4096, rope_theta=1e6, norm_eps=1e-5)
OLMOE = llama.LlamaConfig(
    vocab_size=50304, dim=2048, n_layers=2, n_heads=16, n_kv_heads=16,
    mlp_dim=1024, max_seq=4096, rope_theta=10000.0, num_experts=64,
    experts_per_token=8, norm_topk_prob=False, qk_norm=True)
AXK1 = llama.LlamaConfig(
    vocab_size=20480, dim=7168, n_layers=2, n_heads=64, n_kv_heads=64,
    mlp_dim=2048, max_seq=4096, rope_theta=10000.0, norm_eps=1e-6,
    num_experts=12, experts_per_token=8, router_scoring="sigmoid",
    routed_scaling_factor=2.5, router_width=192, n_shared_experts=1,
    n_dense_layers=1, dense_mlp_dim=18432, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_scaling=YarnScaling(
        32.0, 4096, mscale=1.0, mscale_all_dim=1.0))
OLMO_HYBRID = llama.LlamaConfig(
    vocab_size=100352, dim=3840, n_layers=4, n_heads=30, n_kv_heads=30,
    mlp_dim=11008, max_seq=65536, norm_eps=1e-6, qk_norm=True,
    full_rope=False, norm_after=True,
    layer_kinds=("linear", "linear", "linear", "full"),
    linear_heads=30, linear_head_dim=96, linear_value_dim=192)
COMMAND_A_PLUS = llama.LlamaConfig(
    vocab_size=32768, dim=4096, n_layers=4, n_heads=128, n_kv_heads=8,
    head_width=128, mlp_dim=4096, max_seq=200000, rope_theta=50000.0,
    norm_eps=1e-5, tie_embeddings=True, num_experts=4,
    experts_per_token=8, router_scoring="sigmoid", router_width=128,
    n_shared_experts=4, shared_experts_average=True, window=4096,
    window_pattern=(True, True, True, False), full_rope=False,
    norm="layer", parallel_block=True)
# shape: (configuration, slots, max_seq[, the chunk its rings hold])
SHAPES = {"mistral-7b": (MISTRAL, 16, 3072), "olmoe-1b-7b": (OLMOE, 16, 3072),
          "ax-k1": (AXK1, 48, 4096),
          "olmo-hybrid-7b": (OLMO_HYBRID, 8, 12288),
          "command-a-plus": (COMMAND_A_PLUS, 16, 32768, 512)}


def bits(x):
    return np.asarray(x.astype(jnp.float32)).view(np.uint32)


def rows_in_company(c, ks, vs, xq, w_kvb, reach, window=0, owns=(437,)):
    """The decode rows' own path (the kernel, where ``_decode_kernel``
    gives it them) over the last layer of ``ks`` / ``vs`` — slabs, or
    with ``window`` a window layer's rings under the ring's mask: row 0
    at each of the positions ``owns`` among short rows, among rows at
    ``reach``, beside an idle slot and alone -> (row 0 bit-equal in all,
    the largest distance of its output from the XLA walk's)."""
    slots, layer, rows = xq.shape[0], ks.shape[0] - 1, ks.shape[2]
    rows_path = jax.jit(
        lambda xq, ks, vs, w_kvb, pos, active: llama._attend_slab(
            xq, ks, vs, layer, None, pos, None, c, w_kvb, window, pos,
            visits=decode_attention.work_list(
                pos, active, llama.ATTEND_BLOCK, rows)))
    walk = jax.jit(lambda xq, ks, vs, w_kvb, pos: llama._attend_slab(
        xq, ks, vs, layer, None, pos, llama._span_blocks(
            jnp.max(pos) + 1, rows), c, w_kvb, window, pos))
    everyone = jnp.ones((slots,), bool)
    same, apart = True, 0.0
    for own in owns:
        pos = jnp.full((slots,), 300, jnp.int32).at[0].set(own)
        far = pos.at[1:].set(reach)
        outs = [rows_path(xq, ks, vs, w_kvb, p, a)
                for p, a in ((pos, everyone), (far, everyone),
                             (far, everyone.at[-1].set(False)),
                             (pos, everyone.at[1:].set(False)))]
        same &= all((bits(out[0]) == bits(outs[0][0])).all() for out in outs)
        apart = max(apart, float(jnp.max(jnp.abs(
            outs[0][0].astype(jnp.float32)
            - walk(xq, ks, vs, w_kvb, pos)[0].astype(jnp.float32)))))
    return bool(same), apart


def check(name, c, slots, max_seq, chunk=0):
    key = jax.random.PRNGKey(11)
    params = jax.jit(llama.init_params, static_argnums=0)(c, key)
    cache = llama.init_kv_cache(c, slots, max_seq, chunk)
    names = tuple(llama.kv_slabs(c))
    for slab, k in zip(names, [*jax.random.split(key, 2),
                               *jax.random.split(jax.random.PRNGKey(13), 2)]):
        cache[slab] = jax.random.normal(
            k, cache[slab].shape, jnp.float32).astype(c.dtype)
    size = min(llama.ATTEND_BLOCK, max_seq)
    total, own = -(-max_seq // size), 437 // size + 1   # row 0 holds 438
    layer = cache[names[0]].shape[0] - 1      # the last that has slabs

    # the attention alone: one compiled program, the bound an argument
    w_kvb = params["layers"]["w_kvb"][0] if c.kv_lora_rank else None
    xq = jax.random.normal(jax.random.PRNGKey(3), (
        slots, c.n_heads, c.head_dim), jnp.float32).astype(c.dtype)
    pos = jnp.full((slots,), 300, jnp.int32).at[0].set(437)
    attend = jax.jit(lambda xq, ks, vs, w_kvb, blocks: llama._attend_slab(
        xq, ks, vs, layer, None, pos, blocks, c, w_kvb))
    outs = [attend(xq, cache[names[0]], cache[names[1]], w_kvb,
                   jnp.int32(blocks)) for blocks in (own, own + 1, total)]
    same_attention = all((bits(out[0]) == bits(outs[0][0])).all()
                         for out in outs)

    # the decode rows' own path: the slabs, and a window layer's rings
    # with row 0 before its ring has wrapped and after
    same_kernel = same_rings = (None, None)
    if llama._decode_kernel(c, None, max_seq):
        same_kernel = rows_in_company(c, cache[names[0]], cache[names[1]],
                                      xq, w_kvb, max_seq - 2)
        if c.window:
            same_rings = rows_in_company(
                c, cache["k_ring"], cache["v_ring"], xq, None, max_seq - 2,
                c.window, owns=(437, 9001))

    # the whole step: the other rows short, one of them near its slab's
    # end, and that one inactive
    step = jax.jit(lambda p, cache, last, active: llama.decode_step(
        p, last, cache, c, active)[0])
    last = jnp.arange(slots, dtype=jnp.int32) + 5

    def logits(lengths, active):
        return step(params, {**cache, "length": jnp.asarray(
            lengths, jnp.int32)}, last, jnp.asarray(active))

    short = [437] + [300] * (slots - 1)
    long_ = short[:-1] + [max_seq - 2]
    on = [True] * slots
    a, b, d = (logits(short, on), logits(long_, on),
               logits(long_, on[:-1] + [False]))

    # the mixed step's two walks (PR 39): a chunk of 64 rows in the last
    # slot behind the decode rows — each part's output against the same
    # part alone, whatever the OTHER part's lengths make its walk
    chunk, ride = 64, slots - 1
    fresh = [jax.random.normal(k, (slots + chunk, *position),
                               jnp.float32).astype(c.dtype)
             for k, position in zip(jax.random.split(key, 2),
                                    llama.kv_slabs(c).values())]
    xq2 = jax.random.normal(jax.random.PRNGKey(5), (
        slots + chunk, c.n_heads, c.head_dim), jnp.float32).astype(c.dtype)
    resting = jnp.asarray(on[:-1] + [False])

    def walks(slabs, lengths, start, n, parts):
        held = {**cache, **dict(zip(names, slabs)), "length": lengths}
        groups = (llama._decode_rows(held, c, resting),
                  llama._chunk_rows(held, c, chunk, ride, start, n))
        lo, hi = {"both": (0, slots + chunk), "decode": (0, slots),
                  "chunk": (slots, slots + chunk)}[parts]
        _, write_attend, _, _ = llama._row_groups(*(
            groups if parts == "both" else groups[parts == "chunk":][:1]))
        return write_attend(held[names[0]], held[names[1]], layer, 0,
                            xq2[lo:hi],
                            fresh[0][lo:hi], fresh[1][lo:hi], w_kvb)[0]

    walks = functools.partial(jax.jit(walks, static_argnums=4), tuple(
        cache[name] for name in names))   # arguments, not constants
    rows = jnp.asarray(short, jnp.int32)
    far = jnp.asarray(long_[:-2] + [max_seq - 2, 300], jnp.int32)
    decode_alone = walks(rows, 0, chunk, "decode")
    chunk_alone = {(at, n): walks(rows, at, n, "chunk")
                   for at, n in ((0, chunk), (max_seq - 200, 40))}
    same_decode_rows = all(
        (bits(walks(rows, at, n, "both")[0]) == bits(decode_alone[0])).all()
        for at, n in chunk_alone)
    same_chunk_rows = all(
        (bits(walks(lengths, at, n, "both")[slots:slots + n])
         == bits(alone[:n])).all()
        for (at, n), alone in chunk_alone.items()
        for lengths in (rows, far))
    print(json.dumps({
        "shape": name, "slots": slots, "max_seq": max_seq, "blocks": total,
        "attention_row0_bit_equal_at_its_own_bound_one_more_and_all":
            bool(same_attention),
        "kernel_row0_bit_equal_among_short_long_idle_rows_and_alone":
            same_kernel[0],
        "ring_kernel_row0_bit_equal_wrapped_or_not_among_short_long_idle"
        "_rows_and_alone": same_rings[0],
        "ring_kernel_row0_max_abs_diff_from_the_walk": same_rings[1],
        "decode_step_row0_bit_equal_short_vs_long_active":
            bool((bits(a[0]) == bits(b[0])).all()),
        "decode_step_row0_bit_equal_short_vs_long_inactive":
            bool((bits(a[0]) == bits(d[0])).all()),
        "max_abs_diff_logits": float(jnp.max(jnp.abs(a[0] - b[0]))),
        "mixed_walks_decode_row0_bit_equal_to_the_decode_walk_alone":
            bool(same_decode_rows),
        "mixed_walks_chunk_rows_bit_equal_to_the_chunk_walk_alone":
            bool(same_chunk_rows)}),
        flush=True)


if __name__ == "__main__":
    print(jax.devices())
    for shape in sys.argv[1:] or SHAPES:
        check(shape, *SHAPES[shape])
