"""How `xing4.0-29b-a4b`'s parity tolerance was set and what it refuses.

    python -m benchmarks.xing4_parity --seeds 1,2,3 [--controls 3] \\
        [--prompts 96,2048] [--slots 2] [--out chiprun_out/xing4_parity.json]

On the chip, one process, at the published widths of
``chipbench/configs/xing4.0-29b-a4b.json``: per seed, weights drawn from
the seed, and per prompt length (the traffic file's ``parity`` prompt by
default: through the engine's own 512-token chunks, then its decode
steps, as ``chipbench.replica.ProbeLLMServer`` does it) readings of the
logits' relative L2 against the plain float32 reference at the probe's
positions, each as the positions' worst (what the replica compares) and
median:

* ``program`` — the engine's programs as they are: must read inside the
  tolerance;
* ``fp8`` — no engine: the reference with its matrices (``phi`` among
  them) rounded to ``float8_e4m3fn``, the nearest precision below the
  stated one, against itself in float32;
* for the first ``--controls`` seeds the programs as they are against a
  reference that computes ANOTHER function of the same weights:
  ``alpha_0`` (the maps' input-dependent term dropped: constants),
  ``res_identity`` (``H_res`` the identity: the streams never mix),
  ``sinkhorn_1`` and ``sinkhorn_19`` (passes of the projection; 20 are
  published), ``no_bias`` (the router's correction bias dropped) and
  ``bf16_maps`` (the maps made of streams and ``phi`` rounded to
  bfloat16, their logits too).

Every control but ``sinkhorn_19`` must read OUTSIDE the tolerance.
``--slots`` makes the engine smaller than the cell's (the programs'
mathematics does not depend on the slot count; the float8 control's
copies of the embedding and the head need the room).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import time

from benchmarks.solar_open2_parity import _through_engine

CELL = "xing4.0-29b-a4b.docqa"
STATIC = ("n_heads", "n_kv_heads", "rope_theta", "norm_eps")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--controls", type=int, default=0,
                        help="seeds that also get the controls")
    parser.add_argument("--prompts", default="",
                        help="prompt lengths (default: the cell's parity)")
    parser.add_argument("--slots", type=int, default=0,
                        help="slots of the engine (0: the cell's)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ant_ray_tpu.llm import LLMEngine
    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    cell = Cell(CELL)
    spec, traffic = cell.config, cell.traffic
    steps = traffic["parity"]["decode_steps"]
    prompts = [int(p) for p in args.prompts.split(",") if p] \
        or [traffic["parity"]["prompt_tokens"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    config = resolve(spec["model"]["factory"])(spec)
    ref = importlib.import_module(spec["reference"]["module"])
    to_reference = resolve(spec["reference"]["params"])
    dims = ref.dims_of(spec)
    draw = jax.jit(llama.init_params, static_argnums=0)
    t0 = time.perf_counter()
    eng = LLMEngine(config, slots=args.slots or traffic["slots"],
                    max_seq=traffic["max_seq"], seed=seeds[0],
                    **spec["serve"]["kwargs"])
    jax.block_until_ready(eng.params)
    print(f"[parity] {jax.devices()[0].device_kind}: engine ready in "
          f"{time.perf_counter() - t0:.1f} s, prompts {prompts} + {steps} "
          f"in chunks of {eng._chunk_tokens}, cache "
          f"{ {k: v.shape for k, v in eng.cache.items()} }", flush=True)
    rows = []

    def rel_l2(got, want):
        err = [float(e) for e in jnp.sqrt(jnp.sum((got - want) ** 2, -1))
               / jnp.sqrt(jnp.sum(want ** 2, -1))]
        return {"median": statistics.median(err), "worst": max(err),
                "by_position": err}

    def keep(row):
        rows.append(row)
        print("[parity] " + json.dumps(
            {k: ({"median": round(v["median"], 4),
                  "worst": round(v["worst"], 4)}
                 if isinstance(v, dict) and "worst" in v else v)
             for k, v in row.items()}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"rows": rows}, f)

    blocks = {}

    def block_of(patched):
        """``ref.block`` jitted, one function a set of patches: jax
        caches a trace by the function, and a patched module has to be
        traced anew (and only once)."""
        key = tuple(sorted(patched or {}))
        if key not in blocks:
            blocks[key] = jax.jit(lambda *a, **kw: ref.block(*a, **kw),
                                  static_argnames=STATIC)
        return blocks[key]

    def reference(params, tokens, first, cast=None, leaves=None,
                  patched=None, **changed):
        """The reference's logits from position ``first`` on.  ``cast``:
        applied to every matrix; ``leaves``: name -> what a layer's leaf
        of that name becomes; ``patched``: name -> what stands in the
        reference module's place of that name while its block is traced."""
        embed, layer, n, norm_f, head = to_reference(params)
        plain = layer

        def layer(i):
            out = plain(i)
            if cast is not None:
                out = {name: leaf if leaf.ndim < 2 else cast(leaf)
                       for name, leaf in out.items()}
            return {name: (leaves[name](leaf) if leaves and name in leaves
                           else leaf) for name, leaf in out.items()}

        if cast is not None:
            embed, head = cast(embed), cast(head)
        held = {name: getattr(ref, name) for name in patched or {}}
        for name, other in (patched or {}).items():
            setattr(ref, name, other)
        try:
            return ref.forward(
                embed, (layer, n), norm_f, head, jnp.asarray(tokens),
                block_fn=block_of(patched),
                **{**dims, **changed})[first:]
        finally:
            for name, mine in held.items():
                setattr(ref, name, mine)

    def zero(leaf):
        return leaf * 0

    def identity(m, passes, eps):
        return jnp.broadcast_to(jnp.eye(m.shape[-1], dtype=m.dtype), m.shape)

    def rounded_maps(x, phi, b, alpha, *rest):
        """``ref.hc_maps`` of streams, ``phi`` and the maps' logits'
        inputs rounded to bfloat16."""
        def low(a):
            return jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)

        with jax.default_matmul_precision("bfloat16"):
            return plain_maps(low(x), low(phi), low(b), low(alpha), *rest)

    plain_maps = ref.hc_maps
    controls = {
        "alpha_0": dict(leaves={"hc_attn_alpha": zero,
                                "hc_mlp_alpha": zero}),
        "res_identity": dict(patched={"sinkhorn": identity}),
        "sinkhorn_1": dict(hc_sinkhorn_iters=1),
        "sinkhorn_19": dict(
            hc_sinkhorn_iters=spec["hc_sinkhorn_iters"] - 1),
        "no_bias": dict(leaves={"router_bias": zero}),
        "bf16_maps": dict(patched={"hc_maps": rounded_maps}),
    }
    for i, seed in enumerate(seeds):
        if i:
            eng.params = None
            eng.params = draw(config, jax.random.PRNGKey(seed))
        params = eng.params
        for prompt in prompts:
            t1 = time.perf_counter()
            tokens = np.random.default_rng([seed, 11]).integers(
                0, config.vocab_size, prompt + steps, dtype=np.int32)
            want = reference(params, tokens, prompt - 1)
            got = _through_engine(eng, tokens, prompt, steps)
            row = {"seed": seed, "prompt": prompt,
                   "program": rel_l2(got, want),
                   "argmax_equal": int(jnp.sum(
                       jnp.argmax(got, -1) == jnp.argmax(want, -1))),
                   "logit_rms": float(jnp.sqrt(jnp.mean(want ** 2)))}
            row["fp8"] = rel_l2(reference(
                params, tokens, prompt - 1, cast=lambda w: w.astype(
                    jnp.float8_e4m3fn).astype(jnp.bfloat16)), want)
            if i < args.controls:
                for name, how in controls.items():
                    row[name] = rel_l2(got, reference(
                        params, tokens, prompt - 1, **how))
            row["seconds"] = time.perf_counter() - t1
            keep(row)
            del want, got
        del params
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[parity] peak bytes in use "
          f"{stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
