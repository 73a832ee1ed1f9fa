"""Serve deployment wrapping the LLM engine (capability mirror of the
reference's OpenAI-compatible serving layer, ref: llm/_internal/serve/
deployments/ + serve/llm/).

``build_llm_deployment`` returns a serve Application; each replica owns
one engine driven by a background :class:`EngineLoop` — concurrent
requests SHARE engine steps (chunked prefill interleaved with decode)
instead of serializing whole generations behind a lock, which is what
gives short requests TTFT isolation from long prompts.  The
request/response dicts follow the OpenAI completions shape (``prompt``
→ ``choices[].text``) so a client of the reference's `ray.serve.llm`
finds the same surface.

Session affinity: a request carrying ``session_id`` keeps its KV slab
across turns on THIS replica (idle slabs offload to the tiered object
store and restore transparently).  Multi-replica session routing rides
the future owner-direct call plane (ROADMAP item 2) — until then, pin
sessions to a replica via handle affinity or num_replicas=1.
"""

from __future__ import annotations

import os
import time

from ant_ray_tpu.llm.engine import PREFILL_CHUNK_TOKENS, EngineLoop, LLMEngine
from ant_ray_tpu.llm.sampling import SamplingParams


def _tree_bytes(tree) -> int:
    import jax  # noqa: PLC0415 — the engine imported it

    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(tree))


class LLMServer:
    """Replica class: one engine + one background engine loop."""

    def __init__(self, model="tiny", *, slots: int = 8,
                 max_seq: int | None = None, tokenizer_name: str | None =
                 None, seed: int = 0, tensor_parallel_size: int = 1,
                 max_waiting: int | None = None,
                 prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                 kv_idle_evict_s: float | None = None,
                 kv_offload="auto"):
        from ant_ray_tpu._private.jax_utils import require_tpu  # noqa: PLC0415
        from ant_ray_tpu.llm.tokenizer import get_tokenizer  # noqa: PLC0415
        from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

        # `llm:init`: this constructor as one forced span of the
        # start-up trace that created the replica (a child of the
        # worker's `actor:init`; alone, a root), its stages the laps
        # below; what it compiles hangs under it.
        with tracing_plane.staged_span("llm:init") as sp:
            # No fallback on the chip path: unless this process is
            # pinned to the CPU backend (it leased no chip, or the whole
            # tree is pinned from outside), the engine runs on a TPU or
            # not at all.
            self._device = require_tpu("LLM replica")
            sp.lap("device_open")       # jax import, backend, devices()
            store = self._resolve_store(kv_offload)
            tokenizer = get_tokenizer(tokenizer_name)
            t_engine = sp.lap("tokenizer")
            self.engine = LLMEngine(
                model, slots=slots, max_seq=max_seq,
                tokenizer=tokenizer, seed=seed,
                tensor_parallel_size=tensor_parallel_size,
                max_waiting=max_waiting,
                prefill_chunk_tokens=prefill_chunk_tokens,
                kv_idle_evict_s=kv_idle_evict_s,
                kv_offload_store=store)
            # the engine waited for both on the device
            sp.lap("weights", t_engine + self.engine.init_s["weights"])
            sp.lap("cache")
            self._loop = EngineLoop(self.engine, max_waiting=max_waiting)
            sp.attrs.update(
                platform=self._device.platform,
                device_kind=self._device.device_kind,
                param_bytes=_tree_bytes(self.engine.params),
                cache_bytes=_tree_bytes(self.engine.cache),
                slots=self.engine.slots, max_seq=self.engine.max_seq)
            sp.lap("loop")

    @staticmethod
    def _resolve_store(kv_offload):
        """"auto" → object plane when this process is a cluster worker,
        host-local otherwise; "object"/"local" force a tier; a store
        instance passes through; None lets the engine default apply."""
        if kv_offload is None or not isinstance(kv_offload, str):
            return kv_offload
        from ant_ray_tpu.llm import kv_offload as kvo  # noqa: PLC0415

        if kv_offload == "local":
            return kvo.LocalKvStore()
        if kv_offload == "object":
            return kvo.ObjectPlaneKvStore()
        if kv_offload == "auto":
            try:
                from ant_ray_tpu._private.worker import global_worker  # noqa: PLC0415

                if global_worker.connected:
                    return kvo.ObjectPlaneKvStore()
            except Exception:  # noqa: BLE001 — no runtime: local tier
                pass
            return kvo.LocalKvStore()
        raise ValueError(f"unknown kv_offload mode {kv_offload!r}")

    @staticmethod
    def _check_deadline(where: str) -> None:
        """Shed a request whose end-to-end deadline (stamped by the
        serve ingress/handle) already expired — generating tokens
        nobody is waiting for would burn engine steps for nothing."""
        from ant_ray_tpu.exceptions import DeadlineExceededError  # noqa: PLC0415
        from ant_ray_tpu.serve.api import get_request_deadline  # noqa: PLC0415

        deadline_ts = get_request_deadline()  # wall-clock wire field
        if deadline_ts is not None and time.time() >= deadline_ts:
            raise DeadlineExceededError(
                f"request deadline expired before {where} — shed, "
                "not executed")

    @staticmethod
    def _deadline_timeout() -> float | None:
        from ant_ray_tpu.serve.api import get_request_deadline  # noqa: PLC0415

        deadline_ts = get_request_deadline()
        if deadline_ts is None:
            return None
        return max(0.0, deadline_ts - time.time())

    @staticmethod
    def _is_chat(request: dict) -> bool:
        path = request.get("__route_path__", "")
        return "messages" in request or path.endswith("/chat/completions")

    def _submit(self, prompt, sampling, session_id=None):
        """Admission (typed shed inside the `llm:admission` span) +
        enqueue to the engine loop."""
        from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

        ctx = tracing_plane.current()
        with tracing_plane.span("llm:admission"):
            return self._loop.submit(prompt, sampling,
                                     session_id=session_id,
                                     trace_ctx=ctx)

    def _wait(self, handle, where: str):
        from ant_ray_tpu.exceptions import DeadlineExceededError  # noqa: PLC0415

        timeout = self._deadline_timeout()
        try:
            return handle.wait(timeout)
        except TimeoutError as exc:
            raise DeadlineExceededError(
                f"request deadline expired during {where}") from exc

    def __call__(self, request: dict) -> dict:
        """OpenAI-shaped request.  Completions: {"prompt": ...} →
        choices[].text.  Chat (/v1/chat/completions or a "messages"
        key): templated through the tokenizer's chat template →
        choices[].message (ref: the OpenAI-compatible serving surface,
        llm/_internal/serve/deployments/llm/llm_server.py).  An
        optional ``session_id`` pins the request to a persistent KV
        session (multi-turn reuse + tiered offload)."""
        if self._is_chat(request):
            return self._chat(request)
        prompts = request.get("prompt", "")
        many = isinstance(prompts, list) and prompts and not isinstance(
            prompts[0], int)
        batch = prompts if many else [prompts]
        sampling = self._sampling(request)
        session_id = request.get("session_id")
        from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

        self._check_deadline("generation")
        with tracing_plane.span(
                "llm:generate",
                {"prompts": len(batch),
                 "max_tokens": sampling.max_tokens}):
            handles = [self._submit(p, sampling, session_id=session_id)
                       for p in batch]
            outs = [self._wait(h, "generation") for h in handles]
        return {
            "object": "text_completion",
            "choices": [
                {"index": i, "text": o.text,
                 "token_ids": o.token_ids,
                 "finish_reason": o.finish_reason}
                for i, o in enumerate(outs)
            ],
        }

    def _chat(self, request: dict) -> dict:
        from ant_ray_tpu.llm.chat import render_chat  # noqa: PLC0415
        from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

        token_ids = render_chat(self.engine.tokenizer,
                                request.get("messages", []))
        sampling = self._sampling(request)
        self._check_deadline("generation")
        with tracing_plane.span(
                "llm:generate",
                {"max_tokens": sampling.max_tokens, "chat": True}):
            handle = self._submit(token_ids, sampling,
                                  session_id=request.get("session_id"))
            out = self._wait(handle, "generation")
        return {
            "object": "chat.completion",
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": out.text},
                "finish_reason": out.finish_reason,
            }],
            "usage": {
                "prompt_tokens": len(out.prompt_token_ids),
                "completion_tokens": len(out.token_ids),
                "total_tokens": (len(out.prompt_token_ids)
                                 + len(out.token_ids)),
            },
        }

    @staticmethod
    def _sampling(request: dict) -> SamplingParams:
        return SamplingParams(
            max_tokens=int(request.get("max_tokens", 64)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            top_p=float(request.get("top_p", 1.0)),
            stop_token_ids=tuple(request.get("stop_token_ids", ())),
            seed=request.get("seed"),
        )

    def stream(self, request: dict):
        """Token-streaming completion: a generator of OpenAI-chunk-shaped
        dicts, consumed through the object plane as a streaming actor
        call (num_returns="streaming") and exposed over SSE by the HTTP
        proxy (ref: serve streaming responses, serve/_private/replica.py
        streaming path).  Tokens stream as the loop produces them —
        other requests keep decoding in the same engine steps."""
        chat = self._is_chat(request)
        if chat:
            from ant_ray_tpu.llm.chat import render_chat  # noqa: PLC0415

            prompt = render_chat(self.engine.tokenizer,
                                 request.get("messages", []))
        else:
            prompts = request.get("prompt", "")
            prompt = prompts[0] if isinstance(prompts, list) and prompts \
                and not isinstance(prompts[0], int) else prompts
        sampling = self._sampling(request)
        from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

        self._check_deadline("streaming generation")
        with tracing_plane.span(
                "llm:stream",
                {"max_tokens": sampling.max_tokens, "chat": chat}):
            handle = self._submit(prompt, sampling,
                                  session_id=request.get("session_id"))
            yield from (self._chat_chunks(handle) if chat
                        else self._chunks(handle))

    def _events(self, handle):
        """Handle events → the engine-stream delta shape."""
        decode = self.engine.tokenizer.decode
        for ev in handle:
            if ev["type"] == "token":
                tok = ev["token_id"]
                yield {"token_id": tok, "text": decode([tok]),
                       "finished": False, "finish_reason": None}
            elif ev["type"] == "error":
                raise ev["error"]
            else:
                out = ev["output"]
                yield {"token_id": None, "text": "", "finished": True,
                       "finish_reason": out.finish_reason,
                       "token_ids": list(out.token_ids),
                       "full_text": out.text}

    def _chunks(self, handle):
        for delta in self._events(handle):
            if delta["finished"]:
                yield {"object": "text_completion.chunk",
                       "choices": [{"index": 0, "text": "",
                                    "finish_reason":
                                        delta["finish_reason"]}],
                       "done": True}
            else:
                yield {"object": "text_completion.chunk",
                       "choices": [{"index": 0, "text": delta["text"],
                                    "token_id": delta["token_id"],
                                    "finish_reason": None}],
                       "done": False}

    def _chat_chunks(self, handle):
        for delta in self._events(handle):
            if delta["finished"]:
                yield {"object": "chat.completion.chunk",
                       "choices": [{"index": 0, "delta": {},
                                    "finish_reason":
                                        delta["finish_reason"]}],
                       "done": True}
            else:
                yield {"object": "chat.completion.chunk",
                       "choices": [{"index": 0,
                                    "delta": {"role": "assistant",
                                              "content": delta["text"]},
                                    "finish_reason": None}],
                       "done": False}

    def end_session(self, session_id: str) -> bool:
        """Drop a session's KV state (slot + offloaded slab).  Routed
        through the engine loop so the teardown runs on the loop thread
        — never concurrently with a step mutating the same slot maps."""
        return self._loop.end_session(session_id)

    def load_signals(self) -> dict:
        """Engine load gauges for signal-targeted autoscaling
        (`AutoscalingConfig.target_signal`): art_llm_tokens_per_s,
        art_llm_queue_depth, art_llm_resident_sessions."""
        return self._loop.stats()

    def device_info(self) -> dict:
        """The device this replica's engine runs on, as jax reports it
        in THIS process."""
        return {"platform": self._device.platform,
                "kind": self._device.device_kind,
                "count": len(self.engine._jax.devices()),
                "pid": os.getpid()}

    def health(self):
        return "ok"

    def shutdown(self) -> None:
        """Stop the engine loop thread (replica teardown / tests)."""
        self._loop.shutdown()


def build_llm_deployment(model="tiny", *, name: str = "llm",
                         num_replicas: int = 1, slots: int = 8,
                         max_seq: int | None = None,
                         tokenizer_name: str | None = None,
                         tensor_parallel_size: int = 1,
                         route_prefix: str | None = "/v1",
                         max_ongoing_requests: int | None = None,
                         max_queued_requests: int = 0,
                         request_timeout_s: float | None = None,
                         max_waiting: int | None = None,
                         autoscaling_config=None,
                         prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                         kv_idle_evict_s: float | None = None,
                         kv_offload="auto"):
    """Application for ``serve.run`` exposing the engine under the
    OpenAI surface: POST /v1/completions and /v1/chat/completions
    (+ streaming via {"stream": true}).

    The overload knobs compose: ``max_ongoing_requests`` /
    ``max_queued_requests`` bound the replica's request gate,
    ``request_timeout_s`` stamps the default end-to-end deadline, and
    ``max_waiting`` bounds the ENGINE's prompt line once every KV slot
    is busy — all sheds surface as 429/RESOURCE_EXHAUSTED with the
    retry hint derived from the measured chunk-drain rate.

    Prompts are ingested in chunks of ``prefill_chunk_tokens`` (64
    unless given); ``kv_idle_evict_s`` turns on
    idle-session offload through ``kv_offload`` ("auto" picks the
    object plane inside a cluster).  ``autoscaling_config`` may target
    the engine's published load signals (see
    `AutoscalingConfig.target_signal`).

    Each replica leases ``TPU = tensor_parallel_size`` chips, so the
    cluster must advertise them (``init(num_tpus=)`` simulates them
    under the tests' whole-tree CPU pin)."""
    from ant_ray_tpu import serve  # noqa: PLC0415

    # One chip per tensor-parallel shard: the lease makes the replica's
    # process the owner of those chips.
    actor_options = {"num_tpus": tensor_parallel_size}
    if max_ongoing_requests is None:
        # Requests share engine steps, so they must overlap in the
        # replica: one thread per KV slot, and headroom so the
        # controller's ongoing()/health() polls never queue behind a
        # generation (a first request compiles for a minute at 1B; three
        # missed polls would eject the replica).  With a request gate
        # the controller sizes the pool from the gate instead.
        actor_options["max_concurrency"] = slots + 8
    dep = serve.deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        route_prefix=route_prefix,
        ray_actor_options=actor_options,
        max_ongoing_requests=max_ongoing_requests,
        max_queued_requests=max_queued_requests,
        request_timeout_s=request_timeout_s,
        autoscaling_config=autoscaling_config)
    return dep.bind(model, slots=slots, max_seq=max_seq,
                    tokenizer_name=tokenizer_name,
                    tensor_parallel_size=tensor_parallel_size,
                    max_waiting=max_waiting,
                    prefill_chunk_tokens=prefill_chunk_tokens,
                    kv_idle_evict_s=kv_idle_evict_s,
                    kv_offload=kv_offload)
