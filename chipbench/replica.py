"""The replica the serving cells deploy: ``LLMServer`` with the request
path untouched, plus only what nothing but the chip's owner can do —
``probe_logits``, ``trace_start`` / ``trace_stop`` / ``trace_reduce``
and ``owner_stats``.  It goes when owner-side profile and memory hooks
land in the program (PERF.md, Open questions).

It is deployed with exactly ``build_llm_deployment``'s options: the
driver calls that function and swaps the class in the application it
returns.  The first argument is the configuration FILE's content, so
that the driver's process never imports jax to build a ``LlamaConfig``.
"""

from __future__ import annotations

import time

from ant_ray_tpu.llm.serve_llm import LLMServer


class ProbeLLMServer(LLMServer):

    def __init__(self, spec: dict, **kwargs):
        from ant_ray_tpu._private.jax_utils import import_jax
        from chipbench.owner import CompileCounter
        from chipbench.spec import resolve

        jax = import_jax()
        self._compiles = CompileCounter(jax)
        self._spec = spec
        self._trace_dir = None
        t0 = time.perf_counter()
        config = resolve(spec["model"]["factory"])(spec)
        super().__init__(config, **kwargs)
        jax.block_until_ready((self.engine.params, self.engine.cache))
        self._init_s = time.perf_counter() - t0

    # ------------------------------------------------------ correctness

    def probe_logits(self, seed: int, prompt_tokens: int,
                     decode_steps: int) -> dict:
        """A seeded sequence prefilled through the engine's cache in the
        engine's own chunks, then decoded ``decode_steps`` steps
        (teacher-forced); logits at every position from the last prompt
        token on, against the plain reference's full forward pass on
        the same weights.  Runs on the engine-loop thread while the
        engine is idle."""
        out = self._loop._call_on_loop(
            lambda eng: self._probe_on_loop(eng, seed, prompt_tokens,
                                            decode_steps), timeout=900.0)
        if out is None:
            raise RuntimeError("the logit probe did not finish")
        return out

    def _probe_on_loop(self, eng, seed, prompt_tokens, decode_steps):
        import importlib

        import numpy as np

        from chipbench.spec import resolve

        jax, jnp = eng._jax, eng._jnp
        if eng.has_unfinished():
            raise RuntimeError("probe_logits needs an idle engine")
        t0 = time.perf_counter()
        vocab = eng.config.vocab_size
        tokens = np.random.default_rng([seed, 11]).integers(
            0, vocab, prompt_tokens + decode_steps, dtype=np.int32)
        slot, chunk = eng._free_slots[-1], eng._chunk_tokens
        got = []
        for start in range(0, prompt_tokens, chunk):
            part = tokens[start:min(start + chunk, prompt_tokens)]
            buf = np.zeros((chunk,), np.int32)
            buf[:len(part)] = part
            logits, eng.cache = eng._prefill_chunk_jit(
                eng.params, eng.cache, jnp.asarray(buf), slot, start,
                len(part))
        got.append(logits)
        mask = np.zeros((eng.slots,), bool)
        mask[slot] = True
        for j in range(decode_steps):
            last = np.zeros((eng.slots,), np.int32)
            last[slot] = tokens[prompt_tokens + j]
            logits, eng.cache = eng._decode_jit(
                eng.params, eng.cache, jnp.asarray(last), jnp.asarray(mask))
            got.append(logits[slot])
        got = jnp.stack(got)
        system_s = time.perf_counter() - t0

        ref = importlib.import_module(self._spec["reference"]["module"])
        embed, layer, n, norm_f, head = resolve(
            self._spec["reference"]["params"])(eng.params)
        block = jax.jit(ref.block, static_argnames=(
            "n_heads", "n_kv_heads", "rope_theta", "norm_eps"))
        want = ref.forward(embed, (layer, n), norm_f, head,
                           jnp.asarray(tokens), block_fn=block,
                           **ref.dims_of(self._spec))[prompt_tokens - 1:]
        err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=-1)) / jnp.sqrt(
            jnp.sum(want ** 2, axis=-1))
        return {"rel_l2": [float(e) for e in err],
                "argmax_equal": int(jnp.sum(
                    jnp.argmax(got, -1) == jnp.argmax(want, -1))),
                "positions": int(got.shape[0]),
                "logit_rms": float(jnp.sqrt(jnp.mean(want ** 2))),
                "system_s": system_s,
                "seconds": time.perf_counter() - t0}

    # ------------------------------------------------------ observation

    def owner_stats(self) -> dict:
        from chipbench.owner import memory_peak_bytes

        eng = self.engine
        return {"compiles": self._compiles.count,
                "engine": dict(eng.stats),
                "init_s": self._init_s,
                "memory_peak_bytes": memory_peak_bytes(eng._jax, 1)}

    def trace_start(self, directory: str) -> None:
        from chipbench.trace_reduce import start_trace

        start_trace(self.engine._jax, directory)
        self._trace_dir = directory
        # Counters as the trace starts, not before: starting the
        # profiler takes seconds, and the engine runs on meanwhile.
        self._trace_t0 = time.perf_counter()
        self._trace_engine0 = dict(self.engine.stats)

    def trace_stop(self) -> dict:
        out = {"host_window_s": time.perf_counter() - self._trace_t0,
               "engine": dict(self.engine.stats),
               "engine_before": self._trace_engine0, "wall": time.time(),
               "chunk_width": self.engine._chunk_tokens}
        self.engine._jax.profiler.stop_trace()
        return out

    def trace_reduce(self) -> dict:
        """After the window: the xplane file -> the reduced trace."""
        from chipbench.trace_reduce import reduce_dir

        return reduce_dir(self._trace_dir)
