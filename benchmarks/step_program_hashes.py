"""Are a tree's step programs another tree's?  Prints, as one JSON
object, a hash of the LOWERED text (StableHLO, no debug info) of the
decode step, the prefill chunk, the mixed step and ``forward`` — for
every preset of ``llama.CONFIGS`` at a tiny cache, and for the serving
cells named (default: one of each kind of block) at their published
sizes, from shapes alone: nothing is allocated, compiled or run, so it
needs no chip (~1 min on the CPU).

    python -m benchmarks.step_program_hashes [cell ...] > change.json
    (cd <a copy of the parent> && PYTHONPATH=. python -m \\
        benchmarks.step_program_hashes [cell ...]) > parent.json
    cmp parent.json change.json

Equal hashes mean the compiler is handed the same program: what a
configuration that a change must not touch compiles to has not changed
(PR 56: ``hc_mult`` 1 traces not an operation more).  A preset or a cell
the tree does not know is left out, not an error: the parent lacks what
a PR adds.

With ``--as-on-the-chip`` first, the programs are those ONE TPU device
runs — lowered for the TPU with ``jax.default_backend`` answering
``tpu``, so the Pallas kernels (``_decode_kernel``, ``_grouped_tile``)
are in them, serialized — and the cells alone (the tiny presets' heads
are no lane tiles): after touching a kernel's file (PR 57).
"""

import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELLS = ("mistral-7b.longprompt", "ax-k1.reason", "sdar-30b-a3b.reason",
         "command-a-plus.docqa", "solar-open2.digest",
         "granite-4.0-h-small.sessions", "ouro-2.6b.rollout")


def hashes(config, slots: int, max_seq: int, chunk: int,
           platform: str = "cpu") -> dict:
    import jax
    import jax.numpy as jnp

    from ant_ray_tpu.models import llama

    c, block = config, config.block_length
    params = jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(c, slots, max_seq))
    last = jax.ShapeDtypeStruct((slots, block) if block else (slots,),
                                jnp.int32)
    active = jax.ShapeDtypeStruct((slots,), bool)
    tokens = jax.ShapeDtypeStruct((chunk,), jnp.int32)

    def store(a):
        return {"store": a} if block else {}

    traced = {
        "decode": jax.jit(lambda p, k, t, a: llama.decode_step(
            p, t, k, c, a, **store(a))).trace(params, cache, last, active),
        "chunk": jax.jit(lambda p, k, t: llama.prefill_chunk_into_cache(
            p, t, k, 1, 0, 3, c)).trace(params, cache, tokens),
        "mixed": jax.jit(lambda p, k, t, n, a: llama.mixed_step(
            p, t, n, k, c, a, 1, 0, 3, **store(a))).trace(
                params, cache, last, tokens, active),
    }
    if not block and platform == "cpu":     # 16 tokens: no flash kernel
        # one sequence, the shape the recurrent presets' tests use
        traced["forward"] = jax.jit(
            lambda p, t: llama.forward(p, t, c)).trace(
                params, jax.ShapeDtypeStruct((1, 16), jnp.int32))
    return {name: hashlib.sha256(program.lower(
        lowering_platforms=(platform,)).as_text().encode()).hexdigest()[:16]
        for name, program in traced.items()}


def _strip_kernel_locations():
    """A kernel is serialized WITH its operations' source locations —
    file paths and lines, another tree's differ — so the serializer is
    handed the module without them (JAX's own internals, for this
    script's comparison alone)."""
    from jax._src import tpu_custom_call
    from jaxlib.mlir.passmanager import PassManager

    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def stripped(module, **kw):
        with module.context:
            PassManager.parse("builtin.module(strip-debuginfo)").run(
                module.operation)
        return serialize(module, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = stripped


def main(argv=None) -> int:
    from ant_ray_tpu.models import llama
    from chipbench.spec import Cell, resolve

    platform = "cpu"
    if argv and argv[0] == "--as-on-the-chip":
        import jax

        platform, argv = "tpu", argv[1:]
        jax.default_backend = lambda: "tpu"
        _strip_kernel_locations()
    out = {name: hashes(config, 3, 64, 16)
           for name, config in llama.CONFIGS.items()
           if config.dim <= 64 and platform == "cpu"}
    for name in (argv if argv else CELLS):
        try:
            cell = Cell(name)
        except SystemExit:
            continue
        config = resolve(cell.config["model"]["factory"])(cell.config)
        out[name] = hashes(
            config, cell.traffic["slots"], cell.traffic["max_seq"],
            cell.config["serve"]["kwargs"].get("prefill_chunk_tokens", 64),
            platform)
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
