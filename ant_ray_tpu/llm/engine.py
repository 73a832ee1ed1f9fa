"""Continuous-batching LLM engine on the framework's own JAX models.

Capability mirror of the reference's vLLM engine integration (ref:
llm/_internal/serve/engines/vllm/vllm_engine.py, batch/stages/
vllm_engine_stage.py) designed for TPU/XLA rather than around CUDA:

* **Static shapes everywhere.**  The decode step is one jitted function
  over a fixed number of slots.  Every prompt is ingested in chunks of
  ``prefill_chunk_tokens`` through ONE compiled `prefill_chunk` variant
  (slot/offset/length all traced), a chunk an iteration beside the
  decode step — a long prompt does not monopolize a step, so
  short-request TTFT does not queue behind it and resident sessions
  keep decoding smoothly during ingestion.
* **Dense per-slot KV slabs** (models/llama.py `init_kv_cache`) instead
  of paged KV: XLA cannot tile dynamic gather-heavy paging the way a
  CUDA kernel can, while dense slabs keep decode attention a plain
  masked matmul on the MXU.  That holds only while nothing copies the
  slabs: a step reads every reserved position of them whatever is
  valid, so reserved slots cost time as well as memory, and the step
  programs update the donated cache in place (they carry it through
  the layer loop; scanned over, it was copied about three times a
  call).  Slot reuse gives the same
  admit-new-work-each-step behavior as paged attention's block reuse.
* **Continuous batching**: each `step()` admits queued prompts, runs at
  most one chunk, and decodes every active slot in one batched call.
* **Three step programs.**  When a chunk is due AND rows decode, the
  iteration dispatches ONE program for both (``_mixed_step``,
  models/llama.py `mixed_step`): the chunk's rows ride the decode step
  through the layers, and every weight is read once where the chunk
  program followed by the decode program read it twice.  With nothing
  decoding the chunk runs alone (``_prefill_chunk``), with no chunk
  due the decode step does (``_decode``).  What decides is what the
  engine observes at that iteration, not an option — and the rows:
  where slots + chunk pass ``RIDE_ROWS`` (a 512-token chunk) the chunk
  program and the decode program run one after the other as before.
* **One decode step ahead.**  The newest token of every slot stays on
  the device and the jitted sampler feeds it to the next step, so an
  iteration dispatches decode step N+1 and only then reads and emits
  step N: the host's work between two steps runs under the device's.
  A finish by count (``max_tokens``, ``max_seq``) is known at dispatch
  and leaves the next step's mask; a stop token is read one step late,
  and the row that step computed for the ended sequence is dropped.
  A prompt's end is one more read, of its first token, and where it
  stands in the iteration is the same in all three programs' cases
  wherever a step is unread: the token is sampled on the device and
  the row joins the decode batch from there, and the read comes
  BEHIND the landing of step N — after the mixed step that carried
  the prompt's last chunk, or after the chunk program AND step N+1,
  which already holds the new row.  Step N's tokens, ready before the
  program that ended the prompt, never wait it out.  Only with no
  step unread (nothing decodes) is the first token read at once,
  before the decode step of the same iteration is dispatched.
* **A step gives a row what its model's rule gives it.**  Most models
  give one token a step.  A model that generates by diffusion over
  blocks (``LlamaConfig.block_length``) runs BLOCK steps in the decode
  step's place, through the same loop: every active slot feeds its
  whole block in flight, ``block_length`` rows behind its stored
  length, and a landing brings 0 to ``block_length`` tokens a row — a
  block's, together, at the landing of the step that filled its last
  place.  The block in flight (its tokens, and which places are still
  masks) lives on the device beside the keys; the sampler's transfer
  rule fills places there.  A block whose last place it fills is
  CLOSING: its final tokens move, on the device, to a second table a
  slot, and the next block starts behind it as masks at once.  The
  step after carries both — ``2 x block_length`` rows a slot, every
  step, one compiled shape: the closing block's rows (live where the
  slot has one) go through the layers once more, so that the keys and
  values stored are those of the final tokens, the length moves over
  them, and the new block's first step sees them as that same step
  wrote them.  A block's STORE pass so rides the next block's first
  step: ``denoising_steps`` passes a block, not one more
  (``stats["blocks_fused"]``).  Block rows with no mask left and
  nothing closing are still a store pass of their own (the length
  moves by a block): what a caller of the step program by hand gets,
  and the engine at a slab's end.  The step decides all of it there,
  from the slot's own tables, so the step dispatched one ahead needs
  nothing the host has not read.  Every finish — ``max_tokens``,
  ``max_seq``, a stop token — is found at a landing, inside the block
  where it falls; the row of the step already dispatched is dropped,
  as a stop token's always was.  A prompt's whole blocks are ingested
  in chunks under the block-causal mask and its tail (``len mod
  block_length``) seeds the first block; no first token is sampled at
  a prompt's end.  Such a model has ONE compiled step program, the
  mixed step — the block rows alone are it with no chunk, a chunk alone
  is it with no slot active (``programs._block_steps``) — so that a
  row's bits, and with them a greedy reply, do not hang on the company
  it had.
* **Session KV offload** (``session_id=`` + kv_offload.py stores): a
  finished request's slab stays RESIDENT in its slot for multi-turn
  reuse; idle sessions are evicted — LRU past ``kv_idle_evict_s`` or on
  KV-full admission pressure — by device-getting the slab to host and
  sealing it into a tiered store (object plane: arena → spill tiers),
  freeing the slot.  The next token for an offloaded session triggers a
  background-thread fetch (the step loop NEVER blocks on a restore;
  decode continues and the slab installs when it lands, attributed via
  the ``llm:restore`` trace span), making resident-session count
  disk-bounded instead of HBM-bounded.  Round trips are bitwise exact:
  restored token streams are identical to uninterrupted runs.
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ant_ray_tpu.llm import programs
from ant_ray_tpu.llm.sampling import (
    BLOCK_COUNTERS,
    BLOCK_FILLED,
    BLOCK_FUSED,
    BLOCK_STORED,
    SamplingParams,
    sampler_work,
    split_read,
)
from ant_ray_tpu.llm.tokenizer import get_tokenizer

logger = logging.getLogger(__name__)


@dataclass
class RequestOutput:
    request_id: str
    prompt_token_ids: list
    token_ids: list = field(default_factory=list)
    text: str = ""
    finished: bool = False
    finish_reason: str | None = None
    error: str | None = None


@dataclass(eq=False)
class _Seq:
    request_id: str
    prompt: list
    sampling: SamplingParams
    slot: int = -1
    generated: list = field(default_factory=list)
    # uint32 (2,) device array while the seq is waiting, prefilling or
    # paused; None while it decodes — its key is then row ``slot`` of
    # the engine's key table and advances inside the jitted sampler.
    rng_key: Any = None
    session: Any = None           # _Session | None
    prefill_done: int = 0         # prompt tokens ingested
    kv_len: int = 0               # slab tokens written for this slot
    last_tok: int | None = None   # newest token read (resume after restore)
    # Decode steps still to dispatch before max_tokens or max_seq ends
    # the sequence: a finish by count is known before its token is read.
    steps_left: int = 0
    on_event: Any = None          # callable(dict) | None — streaming sink
    trace_ctx: Any = None         # TraceContext for llm:* spans
    # Marks for the llm:engine stage span (perf_counter, engine step):
    submitted: tuple = ()         # (wall, perf_counter, step) at submit
    first_chunk: tuple | None = None   # first prefill dispatch
    first_token: tuple | None = None   # first token handed to on_event
    chunks: int = 0               # chunks dispatched
    # Under a sampled trace_ctx only: (perf_counter, the engine's
    # chunks so far, its prompt ends so far) a token handed over, the
    # first token's too -> the span's emit_ms, chunk_gaps and
    # prompt_end_gaps.
    emits: list | None = None
    # Generation by diffusion over blocks only: the leading places of
    # the block in flight that the prompt's tail decided (handed to
    # nobody), the blocks stored, those of them stored by a step that
    # also ran the next block, and the block steps that carried the row
    # (the llm:engine span's ``blocks`` / ``blocks_fused`` /
    # ``block_row_steps``).
    block_known: int = 0
    blocks: int = 0
    blocks_fused: int = 0
    block_row_steps: int = 0


@dataclass(eq=False)
class _Session:
    """A logical conversation owning (at most) one KV slot over time."""

    session_id: str
    state: str = "new"            # new|resident|offloaded|restoring|failed
    slot: int = -1
    kv_len: int = 0               # tokens in the (resident or offloaded) slab
    carry: list = field(default_factory=list)  # final token, KV not written
    last_used: float = 0.0
    handle: Any = None            # offload store handle
    current: _Seq | None = None   # seq owning the slot right now
    paused: _Seq | None = None    # mid-generation seq parked by eviction
    pending: list = field(default_factory=list)  # seqs awaiting the slab


PHASES = ("drain", "admit", "chunk", "decode", "sample", "fetch", "emit",
          "housekeeping", "idle_wait")
# An iteration that landed a decode step and took longer than this kept
# its rows standing still: four times the longest step any benchmark
# cell runs (62.6 ms).
STALL_S = 0.25
# The chunk a prompt is ingested in where the caller names no width:
# Serve's, and that of the five serving cells whose chunk rides.
PREFILL_CHUNK_TOKENS = 64
# A chunk rides a decode step (one program for both) while the step's
# rows, slots + chunk, stay at or under this.  Two reasons, the second
# the one that binds.  Under the chip's ridge (v5e: 197 T operations a
# second over 819 GB/s = 240 a byte, so ~240 rows on 2-byte weights) a
# step is bound by its weights' bytes and the chunk's rows come almost
# free; above it the chunk is bound by its arithmetic and riding saves
# only the decode step's read, the smaller part.  And a reply must not
# depend on the company its prompt had: a row's bits follow the rows of
# its program — the norms' float32 reductions are ordered by the
# operand's row count (PR 39 / PR 44), and at 256 rows one element of
# the residual behind ``attn @ wo`` still rounds otherwise in a
# row-layer in a thousand (PR 54); ``llm/programs.py`` tells what was
# measured, and why the programs are three.
RIDE_ROWS = 256


class _PhaseRecorder:
    """The engine loop's account of its own time; always on.

    One phase is open at a time — ``enter(name)`` closes the one before
    it — so the phases tile an iteration with no holes.  A phase's wall
    seconds go to ``stats["phase_<name>_s"]``, and the phase is a
    ``jax.profiler.TraceAnnotation("engine:<name>", step=n)`` under one
    ``StepTraceAnnotation("engine", step_num=n)`` per iteration: host
    events of the profiler's own trace, so on the device events' clock;
    with no trace running they cost a flag test.  ``n`` is
    ``stats["steps"]``, the iterations that dispatched a program so far.

    ``to_host`` is the one place the engine blocks on a device value:
    it counts the read (``d2h_syncs``) and adds its wall time to
    ``block_s`` and to the open phase's ``block_<name>_s``, so host time
    proper is ``sum(phase_*_s) - block_s - phase_idle_wait_s``.  A
    decode step reads once, its tokens in ``fetch``; a prompt's end
    reads its first token in ``chunk`` — a second stretch of that
    phase behind ``fetch`` and ``emit`` where a step was unread, so
    ``block_chunk_s`` holds what was left of the program that ended
    the prompt after step N's tokens had gone out; ``sample`` only
    dispatches.

    The decode step is pipelined one deep, so an iteration's phases run
    ``decode`` and ``sample`` (dispatch step N+1), then ``fetch`` (read
    step N) and ``emit`` (step N's tokens to their callers).  The read
    waits only for what is left of step N after the host's own work of
    the iteration: ``block_fetch_s`` is the remainder of the device
    step that the host did not cover, not the step.
    ``decode_ahead_steps`` counts the decode steps dispatched while the
    step before them was still unread.  ``prompt_ends`` counts the
    prompts whose LAST chunk was dispatched, alone or riding, raised
    at that dispatch (``chunks`` counts every chunk there): what a
    sampled request's hand-overs are stamped with, beside ``chunks``.
    ``decode_span_positions`` adds
    up, per decode step, the positions of a slab its attention walks
    (whole blocks up to the longest active row, from the host's own
    ``kv_len``: no read), ``decode_slab_positions`` the slab's.
    ``decode_walk_positions`` adds up, per decode step, that span times
    the slots — what a walk bound by the longest row reads of a layer's
    slabs — and ``decode_read_positions`` what the dispatched program
    reads of them: where the step's rows attend through
    ``ops/pallas/decode_attention.py`` (``llama._decode_kernel``: full
    slabs, latent ones too, one TPU device) the sum of each ACTIVE
    row's own whole blocks, an idle slot nothing; where they take the
    XLA walk (a mesh, any other backend) the same as
    ``decode_walk_positions``.  Of a
    model with window layers (``LlamaConfig.window``; every other
    leaves these three at zero) ``full_span_positions`` and
    ``window_span_positions`` add up, per decode step AND per chunk,
    the positions walked on its full layers and on its window layers'
    rings (each a layer's walk times the layers of its kind; the same
    rule, no read), and ``decode_rows_past_window`` the active rows of
    a decode step whose context exceeds the window; the two
    ``decode_*_positions`` count the full layers' walk, and
    ``window_walk_positions`` / ``window_read_positions`` are their like
    of the window layers' rings, per decode step: window layers x slots
    x the ring's whole blocks up to the longest active row — what the
    XLA walk reads of the rings — and what the dispatched program
    reads of them: window layers x the sum of each ACTIVE row's own
    whole ring blocks where the rows go through the kernel (all the
    ring's once it has wrapped), the walk's figure where they walk.  Of a model
    with recurrent layers — linear, state-space or gated
    short-convolution ones, the last with a convolution tail for all
    its state (``LlamaConfig.n_recurrent``; every other leaves these
    five at zero), whose state is a slot's and not a position's:
    ``recurrent_decode_rows`` adds up, per decode step, the active rows
    times the recurrent layers — the states the step advanced — and
    ``recurrent_slot_rows`` the slots times the recurrent layers, the
    states its program read and wrote; ``recurrent_chunk_tokens`` and
    ``recurrent_chunk_rows`` a chunk's real tokens and its width, times
    the recurrent layers; ``recurrent_resets`` the chunks that began a
    prompt and so began from an empty state (the host's own ``start``:
    no read).  A recurrent layer walks nothing: the
    ``decode_*_positions`` of such a model are its softmax layers'
    walk.
    ``sample_plain_steps`` and ``sample_sorted_steps`` count the decode
    steps whose sampler drew without a filter and with a sort
    (``sampler_work`` of the step's rows: no read); the rest took the
    arg-max alone.

    An iteration that landed a decode step — rows were waiting for its
    tokens — and took longer than ``STALL_S`` leaves a record of its
    own, whatever is sampled: one warning line in the worker's log and
    a forced ``llm:stall`` span under a trace id minted for it, with
    the ``engine`` event's ``step``, the ``phase`` whose one stretch
    was the longest and its ``phase_s``, the iteration's ``blocked_s``
    (near the whole: the device or the runtime stood still, or another
    thread kept the GIL from the read's return; near zero: the host
    did) and the ``rows`` it kept waiting.  With no row decoding the
    host dispatches a lone prompt's chunks ahead of the device and the
    read at its end waits them all out: long, and nothing stands still.
    A steady iteration pays one comparison for it, and one a phase for
    the longest stretch.
    """

    def __init__(self, jax, stats: dict):
        self._stats = stats
        self._annotation = jax.profiler.TraceAnnotation
        self._step_annotation = jax.profiler.StepTraceAnnotation
        self._keys = {p: (f"engine:{p}", f"phase_{p}_s", f"block_{p}_s")
                      for p in PHASES}
        # Declared here, once: dict(stats) on another thread never sees
        # the dict change size.
        for key in ("steps", "decode_steps", "decode_slots",
                    "decode_ahead_steps", "decode_span_positions",
                    "decode_slab_positions", "decode_walk_positions",
                    "decode_read_positions", "window_span_positions",
                    "full_span_positions", "decode_rows_past_window",
                    "window_walk_positions", "window_read_positions",
                    "recurrent_decode_rows", "recurrent_slot_rows",
                    "recurrent_chunk_tokens", "recurrent_chunk_rows",
                    "recurrent_resets", "sample_plain_steps", "sample_sorted_steps",
                    "d2h_syncs"):
            stats[key] = 0
        stats["block_s"] = 0.0
        for _, phase_key, block_key in self._keys.values():
            stats[phase_key] = stats[block_key] = 0.0
        self._phase = self._span = self._step_span = None
        self._t = 0.0
        self.dispatched = False
        self.landed = 0       # rows of the decode step(s) it read
        # For a stall's record: the iteration's start (perf_counter,
        # block_s) and its longest stretch (phase, seconds).
        self._began = (0.0, 0.0)
        self._longest = (None, 0.0)

    def begin(self) -> bool:
        """Open an iteration; False when one is open already (the
        EngineLoop opened it around ``LLMEngine.step``)."""
        if self._step_span is not None:
            return False
        now = time.perf_counter()
        self._close_phase(now)                   # an open idle_wait
        self.dispatched = False
        self.landed = 0
        self._began = (now, self._stats["block_s"])
        self._longest = (None, 0.0)
        self._step_span = self._step_annotation(
            "engine", step_num=self._stats["steps"])
        self._step_span.__enter__()
        return True

    def end(self) -> None:
        now = time.perf_counter()
        self._close_phase(now)
        self._step_span.__exit__(None, None, None)
        self._step_span = None
        if self.landed and now - self._began[0] > STALL_S:
            self._record_stall(now)
        if self.dispatched:
            self._stats["steps"] += 1
            if self._stats["steps"] == 1:
                # From the first completed step on, a compilation is
                # one a request waits for: `jit:compile` says so.
                from ant_ray_tpu.observability import compile_watch  # noqa: PLC0415

                compile_watch.mark_ready()

    def _record_stall(self, now: float) -> None:
        began, blocked = self._began
        stats, (phase, phase_s) = self._stats, self._longest
        dur = now - began
        attrs = {"step": stats["steps"], "phase": phase,
                 "phase_s": round(phase_s, 4),
                 "blocked_s": round(stats["block_s"] - blocked, 4),
                 "rows": self.landed}
        logger.warning(
            "llm engine step %d stood still: %.3f s, %.3f of them in "
            "%s, %.3f blocked on the device, %d decode rows waiting",
            attrs["step"], dur, phase_s, phase, attrs["blocked_s"],
            attrs["rows"])
        try:
            from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

            # forced (kept whatever the sampling coin says, and no
            # error): no sampled request needed, a trace id of its own
            # artlint: disable=banned-apis — span `ts` is a cross-
            # process wall-clock wire field, the iteration's start
            ts = time.time() - dur
            tracing_plane.record_span(
                tracing_plane.mint(sampled=False), "llm:stall",
                ts=ts, dur_s=dur, attrs=attrs, forced=True)
        except Exception:  # noqa: BLE001 — tracing is best-effort
            pass

    def enter(self, name: str) -> None:
        now = time.perf_counter()
        if name == self._phase:
            # The stretch goes on (the loop's idle wait woke with no
            # work): one event, its seconds kept current.
            self._stats[self._keys[name][1]] += now - self._t
            self._t = now
            return
        self._close_phase(now)
        self._phase, self._t = name, now
        self._span = self._annotation(self._keys[name][0],
                                      step=self._stats["steps"])
        self._span.__enter__()

    def _close_phase(self, now: float) -> None:
        if self._phase is not None:
            held = now - self._t
            self._stats[self._keys[self._phase][1]] += held
            if held > self._longest[1]:
                self._longest = (self._phase, held)
            self._span.__exit__(None, None, None)
            self._phase = None

    def back_to(self, name: str | None) -> None:
        """Reopen the phase a detour interrupted (None: none was open)."""
        if name is None:
            self._close_phase(time.perf_counter())
        else:
            self.enter(name)

    def to_host(self, value):
        """Every blocking device→host read of the engine, counted."""
        t0 = time.perf_counter()
        out = np.asarray(value)
        dt = time.perf_counter() - t0
        stats = self._stats
        stats["d2h_syncs"] += 1
        stats["block_s"] += dt
        if self._phase is not None:
            stats[self._keys[self._phase][2]] += dt
        return out


class LLMEngine:
    """Synchronous engine core; Serve replicas and batch stages drive it.

    ``model`` is a config name from models/llama.CONFIGS, a LOCAL
    CHECKPOINT DIRECTORY (HF Llama layout — real weights, loaded via
    models/checkpoint.py), or a LlamaConfig; ``params`` overrides both
    (random init remains the default for named configs: tests/bench).

    Which rows attend how (``llama._attend_slab``): a decode step's rows
    — in ``_decode`` and as the decode rows of ``_mixed_step`` — read,
    over the full slabs — keys and values, or latents and rotary keys —
    on one TPU device, each ACTIVE row's own blocks as far as that row's
    length through ``ops/pallas/decode_attention.py`` — and over a
    window layer's rings the same, under the ring's mask; a chunk's
    rows, a mesh and every other backend walk in XLA, every slot as far
    as the longest live row.
    ``stats["decode_walk_positions"]`` counts what the second rule
    reads of a decode step's slabs, ``stats["decode_read_positions"]``
    what the dispatched program reads, ``window_walk_positions`` /
    ``window_read_positions`` the same of the rings
    (``_PhaseRecorder``).

    A step is not always one token a row.  Of a model that generates
    by diffusion over blocks (``LlamaConfig.block_length``, the module
    docstring) the decode step is a BLOCK step: ``block_length`` rows a
    slot, a landing that hands a row none or a whole block of tokens,
    and the transfer rule in the sampler (``sampling.block_sampler``).  The
    phases, ``decode_steps`` / ``decode_slots`` and the one read a step
    are the decode step's; beside them ``stats`` counts
    ``block_steps`` (programs dispatched with block rows),
    ``block_rows`` (row-steps: active slots summed over them),
    ``block_store_rows`` (of them store passes and nothing else: how
    often a store did NOT ride a next block's step),
    ``block_places_filled`` and ``block_places_confident`` (places that
    took their draw; those that took it because its probability passed
    the threshold) — the last three counted on the device and read with
    the step's tokens — ``blocks_stored`` (blocks whose store landed
    for a live sequence) and ``blocks_fused`` (those of them stored by
    a step that also ran their slot's next block).
    ``tokens_generated`` stays the tokens handed to callers, and the
    ``decode_*_positions`` count a slot's block as one reader of its
    slab, from the host's stored length (a block stored by the step not
    yet read is one block short), and a closing block's rows as a walk
    of their own, counted where their step lands.  Every step of such a
    model is the mixed program
    (``programs._block_steps``), so the routing counters' decode-only part
    (``moe_decode_*``) stands still and the overall ones are the block
    steps' own.  Sessions are refused: a turn that ends inside a
    block would leave the next turn half a block to carry.
    """

    def __init__(self, model="tiny", params=None, *, slots: int = 8,
                 max_seq: int | None = None, tokenizer=None,
                 seed: int = 0, tensor_parallel_size: int = 1,
                 mesh=None, max_waiting: int | None = None,
                 prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                 kv_idle_evict_s: float | None = None,
                 kv_offload_store=None):
        """``tensor_parallel_size > 1`` makes the ENGINE build a tp mesh
        over this process's local devices and shard params + KV slabs
        itself (ref: vllm_models.py:222 tensor_parallel_size — serving
        an 8B on a slice needs no caller-side sharding).  ``mesh``
        overrides it with a prebuilt mesh (e.g. tp×sp for long-prompt
        prefill via ring attention — forward() switches on sp>1).

        ``prefill_chunk_tokens``: the fixed width of the chunks every
        prompt is ingested in, a positive int.
        ``kv_idle_evict_s``: evict a session's slab after this many
        seconds idle (None disables the LRU sweep; admission pressure
        evicts an idle session either way).
        ``kv_offload_store``: a kv_offload.py store (LocalKvStore /
        ObjectPlaneKvStore); defaults to a LocalKvStore built lazily on
        first eviction.
        """
        from ant_ray_tpu._private.jax_utils import import_jax

        t_init = time.perf_counter()
        if type(prefill_chunk_tokens) is not int or prefill_chunk_tokens < 1:
            raise ValueError(
                "prefill_chunk_tokens must be a positive int (the width "
                f"of a prefill chunk), got {prefill_chunk_tokens!r}")
        self._jax = jax = import_jax()
        import jax.numpy as jnp  # noqa: PLC0415

        self._jnp = jnp
        from ant_ray_tpu.models import llama  # noqa: PLC0415

        self._llama = llama
        loaded = None
        if isinstance(model, str):
            from ant_ray_tpu.models import checkpoint as ckpt  # noqa: PLC0415
            from ant_ray_tpu.models.llama import CONFIGS  # noqa: PLC0415

            if params is not None and model not in CONFIGS:
                # Explicit (e.g. pre-sharded) params: only the config is
                # needed — don't read gigabytes of weights to drop them.
                if not os.path.isdir(model):
                    raise ValueError(
                        f"model {model!r} is neither a named config "
                        f"{sorted(CONFIGS)} nor a local checkpoint "
                        "directory")
                self.config = ckpt.config_from_hf(model)
            else:
                loaded, self.config = ckpt.resolve_model(model)
            if tokenizer is None and model not in CONFIGS:
                tokenizer = get_tokenizer(model)  # checkpoint dir
        else:
            self.config = model
        self.max_seq = min(max_seq or self.config.max_seq,
                           self.config.max_seq)
        self.slots = slots
        # Places of a slot's block in flight (0: one token a step).
        self._block = block = self.config.block_length
        if block and prefill_chunk_tokens % block:
            raise ValueError(
                f"prefill_chunk_tokens {prefill_chunk_tokens} is no "
                f"multiple of block_length {block}: a chunk holds whole "
                "blocks, from a block's first place on")
        self.tokenizer = tokenizer or get_tokenizer(None)
        if params is None:
            params = (loaded if loaded is not None
                      else programs.random_params(
                          self.config, jax.random.PRNGKey(seed)))
        self.mesh = mesh
        if tensor_parallel_size > 1 and mesh is None:
            from ant_ray_tpu.parallel.mesh import build_mesh  # noqa: PLC0415

            self.mesh = build_mesh(
                devices=jax.local_devices()[:tensor_parallel_size],
                tp=tensor_parallel_size)
        self.params = params
        # `llm:init`'s `weights` stage is the device's seconds, not the
        # dispatch's: wait here, once, and once more behind the cache.
        jax.block_until_ready(params)
        t_cache = time.perf_counter()
        self.cache = llama.init_kv_cache(self.config, slots, self.max_seq,
                                         prefill_chunk_tokens)
        # A window layer's ring rows (0: the model has none), as the
        # cache was made.
        self._ring = llama.ring_positions(self.config, self.max_seq,
                                          prefill_chunk_tokens)
        # Whether a decode step's rows read their own blocks alone (the
        # kernel) or every slot's as far as the longest (the walk): what
        # ``decode_read_positions`` and ``window_read_positions`` count.
        self._decode_kernel = llama._decode_kernel(self.config, self.mesh,
                                                   self.max_seq)
        # Per-slot sampling keys, resident on the device: a key enters
        # its row when its sequence joins the decode batch, the jitted
        # sampler splits every active row each step, and the row leaves
        # as a device slice if the sequence is evicted unfinished.  The
        # host never reads a key.
        self._keys = jnp.zeros((slots, 2), jnp.uint32)
        # Per-slot newest tokens, resident on the device beside the
        # keys: a prompt's first token enters its row when the sequence
        # joins the decode batch, and the jitted sampler writes every
        # active row's token back, so step N+1 is fed from step N on
        # the chip and can be dispatched before the host has read N.
        # Of a model that generates by diffusion over blocks the table
        # holds a slot's whole block in flight, (slots, block_length),
        # and ``_masked`` beside it which of its places are still to
        # decide; ``_closed`` the final tokens of the slot's block that
        # is CLOSING and ``_closing`` whether it has one (the next step
        # stores it).  The sampler's transfer rule writes all four.
        self._last = jnp.zeros((slots, block) if block else (slots,),
                               jnp.int32)
        self._masked = jnp.zeros((slots, block), bool) if block else None
        self._closed = self._last if block else None
        self._closing = jnp.zeros((slots,), bool) if block else None
        if self.mesh is not None:
            self._shard_state()
        # Each slot's (temperature, top_k, top_p), kept current when a
        # sequence joins the decode batch; uploaded again only after a
        # change.
        self._sampling_rows = [(0.0, 0, 1.0)] * slots
        self._sampling_dev = None     # (temps, top_ks, top_ps) on device
        # Admission bound: with every KV slot busy, at most this many
        # requests may wait for one (None = unbounded, legacy).  Serving
        # paths set it so a traffic spike sheds typed BackPressureError
        # at admission instead of queueing prompts toward OOM.
        self._max_waiting = max_waiting
        self._free_slots = list(range(slots))
        # slot -> seq: the rows of the NEXT decode step.  A sequence
        # leaves at the dispatch of its last step by count, or when a
        # stop token is read; its slot is freed when its last token is.
        self._active: dict[int, _Seq] = {}
        # The mask of ``_active`` on the device and its rows as a tuple,
        # made again only after ``_active`` changed (None).
        self._active_dev = None
        self._rows: tuple = ()
        # The decode step dispatched and not yet read: (the sampler's
        # output on the device, the (slot, seq) rows it was dispatched
        # for).  Tokens are attributed by those rows, never by
        # ``_active`` as it is when they are read.
        self._flight: tuple | None = None
        self._waiting: list[_Seq] = []
        self._finished: list[RequestOutput] = []
        self._req_counter = itertools.count()
        self._base_key = jax.random.PRNGKey(seed ^ 0x5EED)

        # ---- chunked prefill + session state
        self._chunk_tokens = prefill_chunk_tokens
        # (a block model's ONE program is the mixed step: its chunk
        # rides whatever the rows, ``programs._block_steps``)
        self._chunk_rides = bool(block) or (
            slots + prefill_chunk_tokens <= RIDE_ROWS)
        self._prefilling: list[_Seq] = []         # the ingest queue
        self._sessions: dict[str, _Session] = {}
        self._kv_idle_evict_s = kv_idle_evict_s
        self._kv_store = kv_offload_store
        self._restoring: dict[str, dict] = {}     # sid -> ticket
        self._chunk_rate: float | None = None     # tokens/s EWMA
        self._last_chunk_t: float | None = None
        # Flat on purpose: readers on other threads take dict(stats),
        # a shallow copy under which a nested dict would alias.
        self.stats = {"tokens_generated": 0, "chunks": 0,
                      "chunks_fused": 0, "chunk_tokens": 0,
                      "prompt_ends": 0, "offloads": 0,
                      "offload_bytes": 0, "restores": 0,
                      "restore_wait_s": 0.0, "restore_failures": 0,
                      "pressure_evictions": 0, "idle_evictions": 0}
        self._rec = _PhaseRecorder(jax, self.stats)
        # A routed model's routing counters (llama.ROUTING_COUNTERS):
        # the step programs add to them on the device, in the cache,
        # and a decode step's one read brings them along with its
        # tokens.  ``_routing_seen`` is that array as last read.
        if self.config.num_experts:
            self.stats.update(dict.fromkeys(llama.ROUTING_COUNTERS, 0))
            self._routing_seen = np.zeros(
                (len(llama.ROUTING_COUNTERS),), np.uint32)
        # A looped model: ``loop_passes``, the passes of every step
        # program dispatched (a reader knows a step's depth without the
        # config); with an exit gate its counters
        # (llama.EXIT_COUNTERS) come with the tokens as the routing
        # counters do — ``exit_pass_sum`` in passes, a float.
        if self.config.loops > 1:
            self.stats["loop_passes"] = 0
        # Several residual streams a token (``LlamaConfig.hc_mult``): the
        # REAL rows whose streams the chunks and the decode steps
        # dispatched read and rewrote, from the host's own books.
        if self.config.hc_mult > 1:
            self.stats.update(hc_chunk_rows=0, hc_decode_rows=0)
        if self.config.exit_gate:
            self.stats.update(exit_rows=0, exit_pass_sum=0.0)
            self._exits_seen = np.zeros(
                (len(llama.EXIT_COUNTERS),), np.uint32)
        # Block steps (the class docstring): ``BLOCK_COUNTERS`` are the
        # sampler's, running on the device in ``_block_counts`` and read
        # with a step's tokens; ``_block_seen`` is that array as last
        # read.
        if block:
            self.stats.update(dict.fromkeys(
                ("block_steps", "block_rows", *BLOCK_COUNTERS,
                 "blocks_stored", "blocks_fused"), 0))
            self._block_counts = jnp.zeros((len(BLOCK_COUNTERS),),
                                           jnp.uint32)
            self._block_seen = np.zeros((len(BLOCK_COUNTERS),), np.uint32)
        # The device programs (``programs.step_programs``: what each is,
        # and which of them a block model folds into one), bound here
        # once: a step looks nothing up.
        run = programs.step_programs(
            self.config, slots=slots, max_seq=self.max_seq,
            chunk=prefill_chunk_tokens, mesh=self.mesh)
        self._decode_jit = run.decode
        self._prefill_chunk_jit = run.prefill_chunk
        self._mixed_step_jit = run.mixed_step
        self._sample_jit = run.sample
        self._extract_jit = run.extract
        self._install_jit = run.install
        self._put_row_jit = run.put_row
        self._take_key_jit = run.take_key
        if block:
            def chunk_alone(params, cache, *chunk):
                # beside the slots' own tables, as they stand when called
                return run.prefill_chunk(params, cache, *self._fed(),
                                         *chunk)

            self._prefill_chunk_jit = chunk_alone
            # a step with no chunk: its tokens on the device, once
            self._no_chunk = (jnp.asarray(run.no_chunk[0]),
                              *run.no_chunk[1:])
        # The mixed program has run (so: is compiled).  Set-up answers
        # its requests one at a time and would never run it; the first
        # chunk that runs alone runs it once, empty (``_chunk_alone``).
        self._mixed_ran = False
        self._one_active = jnp.ones((1,), bool)   # _sample_one's mask
        jax.block_until_ready((self.cache, self._keys, self._last))
        # Seconds of this constructor, for the `llm:init` span of
        # whoever builds the engine (``LLMServer``): the weights to the
        # device, then slabs, states and counters on it.
        self.init_s = {"weights": t_cache - t_init,
                       "cache": time.perf_counter() - t_cache}

    def _shard_state(self):
        """Distribute params and KV slabs over the engine's mesh: params
        by the model's logical-axis rules (heads/mlp over tp), slabs
        (a window model's rings too) by kv-head over tp — decode
        attention then runs fully sharded with
        XLA inserting the one all-reduce per block (ref capability:
        vLLM tensor_parallel_size, engine-owned sharding)."""
        jax = self._jax
        from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: PLC0415

        mesh = self.mesh
        tp = mesh.shape.get("tp", 1)
        if self.config.n_recurrent:
            raise ValueError(
                "a recurrent state (linear-attention or state-space "
                "layers, or a short convolution's tail) is not sharded: "
                "the engine runs such a model on one device only "
                "(tensor_parallel_size=1, no mesh)")
        if self.config.kv_lora_rank:
            raise ValueError(
                "a latent (MLA) cache has no heads axis to shard: the "
                "engine runs latent attention on one device only "
                "(tensor_parallel_size=1, no mesh)")
        if self.config.block_length:
            raise ValueError(
                "a block in flight (block_length: generation by diffusion "
                "over blocks) is not sharded: its places go through the "
                "slabs folded among the query heads, which no mesh was "
                "measured with (tensor_parallel_size=1, no mesh)")
        if self.config.hc_mult > 1:
            raise ValueError(self._llama.HC_NO_MESH)
        if self.config.n_kv_heads % tp or self.config.n_heads % tp:
            raise ValueError(
                f"tensor_parallel_size={tp} must divide n_heads="
                f"{self.config.n_heads} and n_kv_heads="
                f"{self.config.n_kv_heads}")
        shardings = self._llama.param_shardings(self.config, mesh)
        self.params = jax.device_put(self.params, shardings)
        # the axis behind the positions: the KV heads, or all of them
        # side by side (``LlamaConfig.flat_kv_heads``), heads-major
        kv = NamedSharding(mesh, P(None, None, None, "tp"))
        rep = NamedSharding(mesh, P())
        slabs = self._llama.kv_slabs(self.config)
        self.cache = {
            name: jax.device_put(x, kv if name in slabs else rep)
            for name, x in self.cache.items()}
        self._keys = jax.device_put(self._keys, rep)
        self._last = jax.device_put(self._last, rep)

    # ------------------------------------------------------------ public

    def add_request(self, prompt, sampling: SamplingParams | None = None,
                    request_id: str | None = None, *,
                    admit: bool = True, session_id: str | None = None,
                    on_event=None, trace_ctx=None,
                    submitted: tuple | None = None) -> str:
        """prompt: str (tokenized here) or token-id list.

        With ``max_waiting`` configured and ``admit=True`` (the serving
        default), a request arriving while every KV slot is busy and the
        waiting line is full is REJECTED with
        :class:`~ant_ray_tpu.exceptions.BackPressureError` — admission
        control at the engine boundary, so overload sheds instead of
        growing an unbounded prompt queue toward OOM.  Before shedding,
        an idle resident session is evicted to the offload store if one
        exists — pressure admits new work by spilling cold state instead
        of refusing.  The retry hint derives
        from the measured chunk-drain rate.  Offline batch paths
        (``generate``) pass ``admit=False``: a caller handing the
        engine a fixed batch wants queueing.

        ``session_id`` attaches the request to a persistent session: its
        KV slab survives the request (multi-turn reuse: the next turn's
        chunks append at its offset) and may be offloaded/restored.
        ``on_event`` streams per-token dicts to the caller (EngineLoop's
        sink); ``trace_ctx`` attributes the `llm:engine` and
        `llm:restore` spans; ``submitted`` is EngineLoop.submit's
        (wall, perf_counter, engine step), where the request's queue
        stage starts (default: now)."""
        if (admit and self._max_waiting is not None
                and not self._free_slots
                and len(self._waiting) >= self._max_waiting
                and not self._evict_for_pressure()):
            from ant_ray_tpu.exceptions import BackPressureError  # noqa: PLC0415

            raise BackPressureError(
                f"engine at capacity: {self.slots} KV slots busy, "
                f"{len(self._waiting)} waiting (max_waiting="
                f"{self._max_waiting})",
                retry_after_s=self.retry_after_hint())
        sampling = sampling or SamplingParams()
        if isinstance(prompt, str):
            token_ids = self.tokenizer.encode(prompt)
        else:
            token_ids = list(prompt)
        if not token_ids:
            raise ValueError("empty prompt")
        budget = max(1, self.max_seq - sampling.max_tokens)
        if len(token_ids) > budget:
            token_ids = token_ids[-budget:]      # keep the suffix
        rid = request_id or f"req-{next(self._req_counter)}"
        seq = _Seq(rid, token_ids, sampling)
        seq.on_event = on_event
        seq.trace_ctx = trace_ctx
        if getattr(trace_ctx, "sampled", False):
            seq.emits = []
        seq.submitted = submitted or (time.time(), time.perf_counter(),
                                      self.stats["steps"])
        if session_id is not None and self.config.n_recurrent:
            # The decode step runs one ahead: a turn that a stop token
            # ends has had its state advanced by that token, and the
            # next turn would ingest it a second time (slabs only
            # overwrite the row; a state cannot take a token back).
            raise ValueError(
                "sessions are not kept over a recurrent state: a model "
                "with linear-attention, state-space or short-convolution "
                "layers serves each request from an empty state (no "
                "session_id)")
        if session_id is not None and self._block:
            # A turn ends where a stop token or max_tokens falls, inside
            # a block: the next turn would begin with part of a block
            # whose other places the ended turn had already decided.
            raise ValueError(
                "sessions are not kept over a block in flight: a model "
                "that generates by diffusion over blocks (block_length) "
                "serves each request from its own prompt (no session_id); "
                "nothing of it is offloaded either")
        if session_id is not None:
            sess = self._sessions.get(session_id)
            if sess is None or sess.state == "failed":
                sess = _Session(session_id)
                self._sessions[session_id] = sess
            seq.session = sess
        seed = sampling.seed
        key = (self._jax.random.PRNGKey(seed) if seed is not None
               else self._jax.random.fold_in(self._base_key, hash(rid)
                                             & 0x7FFFFFFF))
        seq.rng_key = key
        self._waiting.append(seq)
        return rid

    def has_unfinished(self) -> bool:
        return bool(self._waiting or self._active or self._prefilling
                    or self._restoring or self._flight
                    or any(s.paused or s.pending
                           for s in self._sessions.values()))

    def step(self) -> list[RequestOutput]:
        """One engine iteration: land finished restores, admit prompts,
        run one chunk of one prompt, dispatch decode step N+1 for all
        active slots, read step N's tokens and emit them, sweep idle
        sessions.  Returns outputs finished since the last call.

        One of three step programs does the device's part: the mixed
        step where a chunk is due and rows decode (the chunk rides
        decode step N+1; a prompt that ends there reads its first token
        behind step N's landing, and its row joins step N+2), the chunk
        program alone where nothing decodes (a prompt that ends there
        reads its first token at once and joins the decode step of the
        same iteration), the decode program alone where no chunk is
        due.  A chunk too wide to ride (``RIDE_ROWS``) runs alone and
        the decode step behind it, two programs an iteration; a prompt
        that ends there beside an unread step joins step N+1 from its
        first token on the device, and the token is read behind step
        N's landing, as in the mixed step's case.

        The decode step runs one ahead of the host: a token comes back
        from the ``step()`` after the one that dispatched it, and while
        a step is in flight ``has_unfinished()`` stays true, so a
        caller that loops on it drains the last one without knowing."""
        rec = self._rec
        mine = rec.begin()       # False under an EngineLoop iteration
        try:
            self._step_inner()
        finally:
            if mine:
                rec.end()
        done, self._finished = self._finished, []
        return done

    def _step_inner(self):
        rec = self._rec
        rec.enter("admit")
        self._poll_restores()
        self._admit()
        rec.enter("chunk")
        chunk, first = self._next_chunk(), None
        if chunk is not None and not (self._active and self._chunk_rides):
            # nothing decodes beside it, or it is too wide to ride
            first = self._chunk_alone(*chunk)
            chunk = None
            if first is not None and self._flight is None:
                # no step is unread: nothing to hand over before it
                self._first_token(*first)
                first = None
        self._decode(chunk, first)
        rec.enter("housekeeping")
        self._sweep_idle()

    def generate(self, prompts, sampling: SamplingParams | None = None,
                 ) -> list[RequestOutput]:
        """Run a batch of prompts to completion (offline inference)."""
        order = [self.add_request(p, sampling, admit=False)
                 for p in prompts]
        outputs: dict[str, RequestOutput] = {}
        while self.has_unfinished():
            for out in self.step():
                outputs[out.request_id] = out
        return [outputs[rid] for rid in order]

    def stream(self, prompt, sampling: SamplingParams | None = None):
        """Incremental generation for one request: yields a dict per new
        token ({"token_id", "text", "finished": False}) and a final
        summary chunk ({"finished": True, "finish_reason", "token_ids",
        "full_text"}) — the serving-side source for SSE token streaming
        (ref capability: vllm engine streaming outputs)."""
        rid = self.add_request(prompt, sampling)
        seq = self._waiting[-1]
        assert seq.request_id == rid
        emitted = 0
        final: RequestOutput | None = None
        while final is None and self.has_unfinished():
            for out in self.step():
                if out.request_id == rid:
                    final = out
            source = final.token_ids if final else seq.generated
            while emitted < len(source):
                tok = int(source[emitted])
                emitted += 1
                yield {"token_id": tok,
                       "text": self.tokenizer.decode([tok]),
                       "finished": False,
                       "finish_reason": None}
        yield {"token_id": None,
               "text": "",
               "finished": True,
               "finish_reason": (final.finish_reason if final
                                 else "length"),
               "token_ids": list(final.token_ids) if final else [],
               "full_text": final.text if final else ""}

    # -------------------------------------------------- sessions public

    def resident_sessions(self) -> int:
        """Live sessions the engine is holding KV state for — resident,
        offloaded, or mid-restore.  Exceeds ``slots`` exactly when
        offload is doing its job."""
        return sum(1 for s in self._sessions.values()
                   if s.state in ("resident", "offloaded", "restoring"))

    def queue_depth(self) -> int:
        """Requests admitted but not yet generating: waiting for a slot,
        mid-prefill, or parked behind a session restore."""
        return (len(self._waiting) + len(self._prefilling)
                + sum(len(s.pending) + (1 if s.paused else 0)
                      for s in self._sessions.values()))

    def chunk_drain_rate(self) -> float | None:
        """Measured prefill-chunk throughput (tokens/s EWMA), the basis
        for KV-full retry hints.  None until the first two chunks."""
        return self._chunk_rate

    def retry_after_hint(self) -> float:
        """BackPressure retry hint: outstanding prompt tokens over the
        measured chunk-drain rate (legacy fallback: 0.5 s)."""
        rate = self._chunk_rate
        if not rate or rate <= 0:
            return 0.5
        outstanding = sum(max(0, self._ingest(s) - s.prefill_done)
                          for s in self._prefilling)
        outstanding += sum(len(s.prompt) for s in self._waiting)
        outstanding += self._chunk_tokens        # the admitted request
        return min(30.0, max(0.05, outstanding / rate + 0.02))

    def evict_session(self, session_id: str, *, force: bool = False
                      ) -> bool:
        """Offload one session's slab now.  Idle sessions always
        qualify; ``force=True`` additionally pauses a mid-GENERATION
        session (its request resumes after an automatic restore —
        bit-identically, since the slab round trip is exact).  Sessions
        mid-prefill are never evictable.  Returns True if evicted."""
        self._land_flight()      # its tokens first: they may end a turn
        sess = self._sessions.get(session_id)
        if sess is None or sess.state != "resident" or sess.slot < 0:
            return False
        cur = sess.current
        if cur is not None:
            if not force or cur in self._prefilling:
                return False
            if self._active.pop(cur.slot, None) is not None:
                self._active_dev = None
            # the key rides the sequence, not the slot: a device slice
            cur.rng_key = self._take_key_jit(self._keys, cur.slot)
            cur.slot = -1
            sess.kv_len = cur.kv_len
            sess.paused = cur
            sess.current = None
        self._offload(sess)
        return True

    def end_session(self, session_id: str) -> bool:
        """Drop a session: frees its slot (if resident) and deletes its
        offloaded slab (if any).  In-flight work is not interrupted —
        call only for idle sessions."""
        self._land_flight()
        sess = self._sessions.pop(session_id, None)
        if sess is None:
            return False
        if sess.slot >= 0 and sess.current is None:
            self._free_slots.append(sess.slot)
            sess.slot = -1
        if sess.handle is not None and self._kv_store is not None:
            try:
                self._kv_store.delete(sess.handle)
            except Exception:  # noqa: BLE001 — best-effort cleanup
                pass
        return True

    def has_evictable(self) -> bool:
        """True if admission pressure could free a slot by evicting an
        idle resident session (the submit-side gate's cheap probe)."""
        return any(s.state == "resident" and s.slot >= 0
                   and s.current is None and s.paused is None
                   for s in self._sessions.values())

    # ---------------------------------------------------- step phases

    def _admit(self):
        """Route waiting requests: park session continuations behind
        restores, and hand every other one a slot — its session's, a
        free one or one that pressure evicts — and the ingest queue."""
        # Sessions parked with work but offloaded: ensure a restore is
        # in flight (covers forced mid-generation eviction).
        for sess in self._sessions.values():
            if sess.state == "offloaded" and (sess.paused or sess.pending):
                self._start_restore(sess)
        i = 0
        while i < len(self._waiting):
            seq = self._waiting[i]
            sess = seq.session
            if sess is not None and sess.state in ("offloaded",
                                                   "restoring"):
                self._waiting.pop(i)
                sess.pending.append(seq)
                if sess.state == "offloaded":
                    self._start_restore(sess)
                continue
            if sess is not None and sess.slot >= 0 and (
                    sess.current is not None or sess.paused is not None):
                self._waiting.pop(i)          # session busy: park
                sess.pending.append(seq)
                continue
            if sess is not None and sess.slot >= 0:
                self._waiting.pop(i)          # resident idle: append
                self._begin_ingest(seq, sess.slot, sess.kv_len)
                continue
            if not self._free_slots and not self._evict_for_pressure():
                i += 1
                continue
            slot = self._free_slots.pop()
            # not pop(i): the eviction above lands the step in flight,
            # and a turn that ends there puts its session's next one
            # at the head of the line
            self._waiting.remove(seq)
            if sess is not None:
                sess.slot = slot
                sess.state = "resident"
            self._begin_ingest(seq, slot, sess.kv_len if sess else 0)

    def _begin_ingest(self, seq: _Seq, slot: int, start: int):
        """``seq`` has ``slot``: its prompt joins the ingest queue, its
        chunks to be written from position ``start`` on."""
        sess = seq.session
        if sess is not None:
            sess.current = seq
            sess.last_used = time.monotonic()
            if sess.carry:
                seq.prompt = sess.carry + seq.prompt
                sess.carry = []
        seq.slot = slot
        seq.kv_len = start
        self._prefilling.append(seq)

    def _ingest(self, seq: _Seq) -> int:
        """The tokens of ``seq``'s prompt that go through the chunk
        program: all of them — or, of a model that generates by
        diffusion over blocks, its whole blocks; the tail seeds the
        first block in flight (``_join_decode``).  A prompt shorter
        than a block still runs ONE chunk, empty: it sets the slot's
        length on the device, which the last occupant left."""
        n = len(seq.prompt)
        return n - n % self._block if self._block else n

    def _next_chunk(self):
        """The next chunk of ONE pending prompt: ``(seq, its tokens
        padded to the chunk width, how many are real)``; the prompt
        stays in the queue until ``_chunk_end`` — or None: nothing
        waits.

        Selection is shortest-remaining-prompt-first (FIFO tiebreak):
        a short interactive prompt's single chunk jumps ahead of a
        long ingest's remaining hundreds, so short TTFT stays flat
        under long-prompt interference.  Long prompts cannot starve —
        they absorb every chunk slot no short is contending for — but
        a sustained flood of short prompts will stall them; that is
        the intended bias for an interactive serving tier."""
        if not self._prefilling:
            return None
        seq = min(self._prefilling,
                  key=lambda s: len(s.prompt) - s.prefill_done)
        chunk = self._chunk_tokens
        part = seq.prompt[seq.prefill_done:min(seq.prefill_done + chunk,
                                               self._ingest(seq))]
        buf = np.zeros((chunk,), np.int32)
        buf[:len(part)] = part
        return seq, self._jnp.asarray(buf), len(part)

    def _chunk_alone(self, seq: _Seq, tokens, n: int):
        """The chunk program by itself: no row decodes beside it.  The
        first time, the mixed program runs once before it, EMPTY — no
        row active, no token of the chunk real, at the slot's own
        length on the device: slabs, states and lengths stay as they
        are — so that it is compiled while requests still come one at a
        time (a server's warm-up), not under the first prompt that
        arrives while rows decode.  Its arguments are of the kinds a
        real mixed step passes (the length read to the host: a Python
        number like ``kv_len``, not the device's scalar), or the first
        real one would miss the program's cache and compile again.
        (A block model's chunk alone IS its mixed program,
        ``programs._block_steps``.)  Returns what ``_chunk_end`` does: the prompt's first token,
        unread, where the chunk ended it."""
        if self._chunk_rides and not self._mixed_ran and not self._block:
            _, _, self.cache = self._mixed_step_jit(
                self.params, self.cache, *self._fed(),
                self._jnp.asarray(np.zeros((self.slots,), bool)), tokens,
                seq.slot, int(self.cache["length"][seq.slot]), 0)
            self._mixed_ran = True
            self._note_passes()
        logits, self.cache = self._prefill_chunk_jit(
            self.params, self.cache, tokens, seq.slot, seq.kv_len, n)
        self._note_passes()
        self._chunk_dispatched(seq, n)
        return self._chunk_end(seq, logits)

    def _chunk_dispatched(self, seq: _Seq, n: int):
        """A program that ingests ``n`` tokens of ``seq`` — the chunk
        program, or the mixed one — was dispatched: the host's books.
        The iteration counts as a step, and a request's first chunk
        ends its queue stage."""
        self._rec.dispatched = True
        seq.chunks += 1
        if seq.first_chunk is None:
            seq.first_chunk = (time.perf_counter(), self.stats["steps"])
        self._note_recurrent(self._chunk_tokens, n, seq.kv_len == 0)
        self._note_streams("hc_chunk_rows", n)
        seq.prefill_done += n
        seq.kv_len += n
        self.stats["prompt_ends"] += seq.prefill_done == self._ingest(seq)
        self._note_walk(seq.kv_len)
        self._note_chunk(n)

    def _chunk_end(self, seq: _Seq, logits):
        """Behind a chunk's dispatch: a prompt's end leaves the queue,
        any other goes to the queue's end.  The prompt that ended has
        its first token sampled on the device and joins the decode
        batch from there, no read: returns ``(seq, token)`` for
        ``_first_token``, which the iteration calls where the read
        keeps nobody waiting — else None."""
        self._prefilling.remove(seq)
        if seq.prefill_done < self._ingest(seq):
            self._prefilling.append(seq)
            return None
        self._rec.enter("chunk")
        if self._block:
            # logits at a place are that place's own token's: a prompt's
            # end draws nothing, its tail is the first block's known
            # places and the block's first step draws the rest
            self._join_decode(seq)
            return None
        token = self._sample_one(seq, logits)
        self._join_decode(seq, token)
        return seq, token

    def _first_token(self, seq: _Seq, token):
        """Read and emit a prompt's first token (``_chunk_end``'s).
        The read is the prompt end's one, in ``chunk`` (the phase the
        caller is in), and waits out the program that made the token:
        the prefill program or the mixed step.  Where a decode step was
        unread when that program was dispatched, the step's tokens were
        handed over first (``_decode``).  A stop token ends the
        sequence here; a decode step dispatched since with its row is
        read like any step whose sequence a stop ended (``_land``)."""
        seq.last_tok = tok = int(self._rec.to_host(token)[0])
        self._rec.enter("emit")
        self._after_token(seq, tok, time.perf_counter())

    def _join_decode(self, seq: _Seq, token=None):
        """``seq`` (slot set) decodes from the next step on: its newest
        token (``token``, (1,) on the device, else ``last_tok``), its
        key and its sampling parameters move into the slot's rows —
        token and key through one small program, no read.  ``token``
        is a prompt's first and not read yet, so not in ``generated``:
        it counts among the tokens made, and a sequence that it ends by
        count (``max_tokens``, ``max_seq``) — known here, on the host —
        does not join."""
        slot, s = seq.slot, seq.sampling
        if self._block:
            places = self._first_block(seq)
            if places is None:
                return
        else:
            made = len(seq.generated) + (token is not None)
            seq.steps_left = min(s.max_tokens - made,
                                 self.max_seq - 1 - seq.kv_len)
            if seq.steps_left <= 0:
                return
        # as float32 holds them: what sampler_work tests on the host
        # is what the sampler tests on the device
        row = (float(np.float32(s.temperature)), s.top_k,
               float(np.float32(s.top_p)))
        if row != self._sampling_rows[slot]:
            self._sampling_rows[slot] = row
            self._sampling_dev = None
        if self._block:
            (self._keys, self._last, self._masked,
             self._closing) = self._put_row_jit(
                self._keys, self._last, self._masked, self._closing,
                seq.rng_key, *places, slot)
        else:
            if token is None:
                token = np.asarray([seq.last_tok], np.int32)
            self._keys, self._last = self._put_row_jit(
                self._keys, self._last, seq.rng_key, token, slot)
        seq.rng_key = None
        self._active[slot] = seq
        self._active_dev = None

    def _first_block(self, seq: _Seq):
        """The block in flight a prompt leaves behind its stored blocks
        (``seq.kv_len``): ``(its tokens, which places are masks)`` for
        the device's tables — the prompt's tail known, every other place
        that the slab has room for a mask.  None where there is no such
        place (the prompt fills ``max_seq``): the sequence ends here, by
        length, with nothing generated."""
        size = self._block
        tail = seq.prompt[self._ingest(seq):]
        room = min(size, self.max_seq - seq.kv_len)
        if len(tail) >= room:
            self._release(seq, "length")
            return None
        seq.block_known = len(tail)
        tokens = np.full((size,), self.config.mask_token, np.int32)
        tokens[:len(tail)] = tail
        places = np.arange(size)
        return tokens, (places >= len(tail)) & (places < room)

    def _fed(self) -> tuple:
        """What the decode step is fed from the device's tables: every
        slot's newest token — or its block in flight and its masks, and
        its block closing and whether it has one."""
        if not self._block:
            return (self._last,)
        return self._last, self._masked, self._closed, self._closing

    def _decode(self, chunk: tuple | None = None,
                first: tuple | None = None):
        """Dispatch step N+1, then read and emit step N: the host's
        turn-around and the transfer run under the device's step.  A
        dispatch that fails still lands the step before it.  ``chunk``
        (``_next_chunk``'s, rows are active) rides step N+1, one
        program.  A prompt's end is read behind step N's landing —
        ``first`` (``_chunk_end``'s), where the chunk program before
        step N+1 ended it, or the riding chunk's own — so that step N's
        tokens, ready before the program that ended the prompt, do not
        wait it out, and their emit work runs under it."""
        flight, self._flight = self._flight, None
        logits, rode = None, False
        try:
            if self._active:
                self._flight, logits = self._dispatch_decode(flight, chunk)
                rode = chunk is not None
        finally:
            if flight is not None:
                self._land(flight)
            if rode:       # (a block model's chunk has no logits)
                first = self._chunk_end(chunk[0], logits)
            elif first is not None:
                self._rec.enter("chunk")
            if first is not None:
                self._first_token(*first)

    def _dispatch_decode(self, flight: tuple | None,
                         chunk: tuple | None = None) -> tuple:
        """One decode step and its sampler for the rows of ``_active``,
        fed from the device's token table; no read.  ``flight`` is the
        step before it where that is still unread.  With ``chunk``
        (``_next_chunk``'s) the step is the mixed program: the chunk's
        rows behind the decode rows, the weights read once for both.
        Returns the flight record and the chunk's logits (None without
        one)."""
        rec, stats = self._rec, self.stats
        rec.enter("decode")
        if self._active_dev is None:
            mask = np.zeros((self.slots,), bool)
            mask[list(self._active)] = True
            self._active_dev = self._jnp.asarray(mask)
            self._rows = tuple(self._active.items())
        rows = self._rows
        chunk_logits = None
        if chunk is not None:
            seq, tokens, n = chunk
            logits, chunk_logits, self.cache = self._mixed_step_jit(
                self.params, self.cache, *self._fed(), self._active_dev,
                tokens, seq.slot, seq.kv_len, n)
            self._mixed_ran = True
            self._chunk_dispatched(seq, n)
            stats["chunks_fused"] += 1
        elif self._block:     # its one program, no chunk
            logits, _, self.cache = self._mixed_step_jit(
                self.params, self.cache, *self._fed(), self._active_dev,
                *self._no_chunk)
        else:
            logits, self.cache = self._decode_jit(
                self.params, self.cache, *self._fed(), self._active_dev)
        rec.dispatched = True
        self._note_passes()
        stats["decode_steps"] += 1
        stats["decode_slots"] += len(rows)
        stats["decode_ahead_steps"] += flight is not None
        if self._block:
            stats["block_steps"] += 1
            stats["block_rows"] += len(rows)
            # a slot's places read its slab together, as far as the
            # block's last; a block stored by the step not yet read, or
            # by this one, is a block more (``_land_blocks`` counts a
            # closing block's own walk)
            contexts = [self._block + seq.kv_len for _, seq in rows]
        else:
            # A row of the unread step is one position further on the
            # device than the host's kv_len, which moves when its token
            # lands.
            unread = {id(seq) for _, seq in flight[1]} if flight else ()
            contexts = [1 + seq.kv_len + (id(seq) in unread)
                        for _, seq in rows]
        self._note_read(contexts)
        self._note_walk(max(contexts), contexts)
        self._note_recurrent(self.slots, len(rows))
        self._note_streams("hc_decode_rows", len(rows))
        work = sampler_work(self._sampling_rows[slot] for slot, _ in rows)
        stats["sample_plain_steps"] += work == 1
        stats["sample_sorted_steps"] += work == 2
        rec.enter("sample")
        sampled = self._sample_all(logits)
        # (a block step's finishes are all found at its landing)
        for slot, seq in () if self._block else rows:
            seq.steps_left -= 1
            if not seq.steps_left:
                # max_tokens or max_seq ends it with this step's token:
                # known now, so the next step's mask leaves the row out
                del self._active[slot]
                self._active_dev = None
        return (sampled, rows), chunk_logits

    def _land(self, flight: tuple):
        """The one read of a dispatched decode step, and its tokens to
        their sequences as the step's own rows name them.  A sequence
        that a stop token ended at the step before has left its slot:
        its row is dropped — the host learns of a stop one step late,
        and the K/V row that step wrote lies where the session's next
        turn writes its carry, or behind the length of a freed slot."""
        sampled, rows = flight
        rec = self._rec
        rec.landed += len(rows)
        rec.enter("fetch")
        toks = rec.to_host(sampled)
        rec.enter("emit")
        now = time.perf_counter()     # a step's tokens leave together
        toks, blocks, counts, riding = split_read(toks, self.slots,
                                                  self._block)
        if self.config.num_experts:
            self._note_routing(riding)
        if self.config.exit_gate:
            self._note_exits(riding)
        if self._block:
            self._land_blocks(toks, blocks, counts, rows, now)
            return
        for slot, seq in rows:
            if seq.slot != slot:
                continue
            # the step wrote the K/V of the token it was fed at kv_len
            seq.kv_len = min(seq.kv_len + 1, self.max_seq)
            seq.last_tok = tok = int(toks[slot])
            self.stats["tokens_generated"] += 1
            self._after_token(seq, tok, now)

    def _land_blocks(self, status, blocks, seen, rows: tuple, now: float):
        """A block step's read (``sampling.split_read``: a status a
        slot, the blocks, the sampler's running counters) to its
        sequences: a row whose step STORED a block — the one closing
        beside its next block's first step (``BLOCK_FUSED``), or the
        one in flight with no mask left — moves ``kv_len`` over it; a
        row whose block FILLED at this step hands its tokens over, in
        position order, behind the places the prompt's tail had decided
        — the first stop token, ``max_tokens`` or ``max_seq`` ends the
        sequence there and what follows in the block is dropped (so are
        the block's closing rows in the step already dispatched); any
        other row's block is still partly masks and nothing is handed
        to anyone.  A step can do both to a row: the stored block lies
        before the filled one."""
        size, stats = self._block, self.stats
        for name, more in zip(BLOCK_COUNTERS,
                              (seen - self._block_seen).tolist()):
            stats[name] += more
        self._block_seen = seen
        closed = []
        for slot, seq in rows:
            if seq.slot != slot:
                continue
            seq.block_row_steps += 1
            if status[slot] & BLOCK_STORED:
                if status[slot] & BLOCK_FUSED:
                    closed.append(seq.kv_len + size)
                    seq.blocks_fused += 1
                seq.kv_len = min(seq.kv_len + size, self.max_seq)
                seq.blocks += 1
                stats["blocks_stored"] += 1
            if status[slot] & BLOCK_FILLED:
                known, seq.block_known = seq.block_known, 0
                for place in range(known, size):
                    seq.last_tok = tok = int(blocks[slot, place])
                    stats["tokens_generated"] += 1
                    self._after_token(seq, tok, now,
                                      seq.kv_len + place + 1)
                    if seq.slot != slot:         # it ended there
                        break
        if closed:
            # the closing rows' walk of the step that landed: each as
            # far as its own block's last, where the step before it
            # left the length
            stats["blocks_fused"] += len(closed)
            self._note_read(closed)

    def _land_flight(self):
        """Land the step in flight now, out of turn: before anything
        reads or moves a slot's state on the host."""
        flight, self._flight = self._flight, None
        if flight is not None:
            phase = self._rec._phase
            self._land(flight)
            self._rec.back_to(phase)

    def _note_routing(self, counters):
        """``counters``: the device's running routing counters as they
        came with a step's tokens (uint32 bits in int32; they wrap) —
        what was added since the last reading goes to ``stats``."""
        now = counters.view(np.uint32)
        for name, more in zip(self._llama.ROUTING_COUNTERS,
                              (now - self._routing_seen).tolist()):
            self.stats[name] += more
        self._routing_seen = now

    def _note_passes(self):
        """A step program was dispatched: of a looped model, the passes
        it runs its rows through."""
        if self.config.loops > 1:
            self.stats["loop_passes"] += self.config.loops

    def _note_exits(self, counters):
        """``counters``: the device's running exit counters as they
        came with a step's tokens (``_note_routing``'s neighbour: a
        looped model is not routed, they are all that rides) — what was
        added since the last reading goes to ``stats``, the expected
        exit passes in passes."""
        now = counters.view(np.uint32)
        rows, passes = (now - self._exits_seen).tolist()
        self.stats["exit_rows"] += rows
        self.stats["exit_pass_sum"] += passes * self._llama.EXIT_PASS_UNIT
        self._exits_seen = now

    def _note_read(self, contexts: list):
        """One walk of a decode step's rows over the full slabs, the
        rows holding ``contexts`` positions each: what the XLA walk
        reads of a layer's slabs — every slot as far as the longest —
        and what the path taken reads (``_decode_kernel``: each row's
        own blocks)."""
        stats = self.stats
        span = self._llama.span_positions(max(contexts), self.max_seq)
        stats["decode_span_positions"] += span
        stats["decode_slab_positions"] += self.max_seq
        walk = self.slots * span
        stats["decode_walk_positions"] += walk
        stats["decode_read_positions"] += self._llama.read_positions(
            contexts, self.max_seq) if self._decode_kernel else walk

    def _note_walk(self, longest: int, decoding=()):
        """A step program was dispatched whose longest live row holds
        ``longest`` positions (``decoding``: a decode step's rows'
        contexts): of a model with window layers, what its attention
        walks, by the device's own rule — and of a decode step, what a
        walk of its rows reads of the rings and what the path taken
        reads (``_note_read``'s like)."""
        if not self._ring:
            return
        span, stats = self._llama.span_positions, self.stats
        n_window, n_full = self.config.slab_layers()
        ring_span = n_window * span(longest, self._ring)
        stats["full_span_positions"] += n_full * span(longest, self.max_seq)
        stats["window_span_positions"] += ring_span
        if not decoding:
            return
        stats["decode_rows_past_window"] += sum(
            n > self.config.window for n in decoding)
        walk = self.slots * ring_span
        stats["window_walk_positions"] += walk
        stats["window_read_positions"] += (
            n_window * self._llama.read_positions(decoding, self._ring)
            if self._decode_kernel else walk)

    def _note_recurrent(self, rows: int, live: int, fresh=None):
        """A step program of ``rows`` rows was dispatched, ``live`` of
        them real (a decode step's slots and its active rows; a chunk's
        width and its tokens, ``fresh``: it began its prompt): of a
        model with recurrent layers, the states it touched and
        advanced."""
        n = self.config.n_recurrent
        if not n:
            return
        stats = self.stats
        if fresh is None:                        # a decode step
            stats["recurrent_slot_rows"] += n * rows
            stats["recurrent_decode_rows"] += n * live
        else:
            stats["recurrent_chunk_rows"] += n * rows
            stats["recurrent_chunk_tokens"] += n * live
            stats["recurrent_resets"] += fresh

    def _note_streams(self, counter: str, rows: int):
        """A step program with ``rows`` real rows was dispatched: of a
        model with several residual streams a token, ``counter``
        (``hc_chunk_rows``: a chunk's tokens, ``hc_decode_rows``: a
        decode step's live rows) counts them."""
        if self.config.hc_mult > 1:
            self.stats[counter] += rows

    def _note_chunk(self, n: int):
        self.stats["chunks"] += 1
        self.stats["chunk_tokens"] += n
        now = time.monotonic()
        if self._last_chunk_t is not None:
            dt = max(now - self._last_chunk_t, 1e-6)
            inst = n / dt
            self._chunk_rate = (inst if self._chunk_rate is None
                                else 0.8 * self._chunk_rate + 0.2 * inst)
        self._last_chunk_t = now

    # ------------------------------------------------- offload/restore

    def _store(self):
        if self._kv_store is None:
            from ant_ray_tpu.llm.kv_offload import LocalKvStore  # noqa: PLC0415

            self._kv_store = LocalKvStore()
        return self._kv_store

    def _evict_for_pressure(self) -> bool:
        """Free one slot by offloading the least-recently-used IDLE
        resident session.  Admission pressure spills cold state instead
        of shedding new work."""
        idle = [s for s in self._sessions.values()
                if s.state == "resident" and s.slot >= 0
                and s.current is None and s.paused is None]
        if not idle:
            return False
        victim = min(idle, key=lambda s: s.last_used)
        self._offload(victim)
        self.stats["pressure_evictions"] += 1
        return True

    def _sweep_idle(self):
        if self._kv_idle_evict_s is None:
            return
        cutoff = time.monotonic() - self._kv_idle_evict_s
        for sess in list(self._sessions.values()):
            if (sess.state == "resident" and sess.slot >= 0
                    and sess.current is None and sess.paused is None
                    and sess.last_used < cutoff):
                self._offload(sess)
                self.stats["idle_evictions"] += 1

    def _offload(self, sess: _Session):
        """Device-get the session's slab and seal it into the offload
        store; the slot returns to the free pool.  The slab is NOT
        zeroed — stale bytes past a future occupant's length are masked
        exactly like reused slots always were."""
        self._land_flight()
        slot = sess.slot
        to_host = self._rec.to_host
        # What the layers keep of the slot (llama.kv_slabs: keys and
        # values, or the latent and its rotary key), then its length.
        # The length is the host's: the device's counts the row of a
        # step dispatched before a stop token was read.
        slab = (*map(to_host, self._extract_jit(self.cache, slot)),
                sess.kv_len)
        sess.handle = self._store().put(sess.session_id, slab)
        sess.slot = -1
        sess.state = "offloaded"
        self._free_slots.append(slot)
        self.stats["offloads"] += 1
        self.stats["offload_bytes"] += sum(a.nbytes for a in slab[:-1])

    def _start_restore(self, sess: _Session):
        if sess.state != "offloaded":
            return
        sess.state = "restoring"
        ticket = {"done": False, "result": None, "error": None,
                  "t0": time.monotonic(), "wall0": time.time()}
        self._restoring[sess.session_id] = ticket
        store, handle = self._store(), sess.handle

        def fetch():
            try:
                ticket["result"] = store.get(handle)
            except BaseException as exc:  # noqa: BLE001 — typed below
                ticket["error"] = exc
            finally:
                ticket["done"] = True

        threading.Thread(target=fetch, daemon=True,
                         name=f"kv-restore-{sess.session_id}").start()

    def _poll_restores(self):
        """Land finished restore fetches: install the slab into a free
        (or pressure-evicted) slot and resume the session's work.  Never
        blocks — unfinished fetches stay in flight while decode
        proceeds; a landed fetch with no slot available retries next
        step."""
        if not self._restoring:
            return
        jnp = self._jnp
        for sid, ticket in list(self._restoring.items()):
            if not ticket["done"]:
                continue
            sess = self._sessions.get(sid)
            if sess is None:
                del self._restoring[sid]
                continue
            if ticket["error"] is not None:
                del self._restoring[sid]
                self._fail_session(sess, ticket["error"], ticket)
                continue
            if not self._free_slots and not self._evict_for_pressure():
                continue                     # retry next step
            slot = self._free_slots.pop()
            del self._restoring[sid]
            *slabs, ln = ticket["result"]
            self.cache = self._install_jit(
                self.cache, tuple(map(jnp.asarray, slabs)),
                jnp.int32(ln), slot)
            dur = time.monotonic() - ticket["t0"]
            self.stats["restores"] += 1
            self.stats["restore_wait_s"] += dur
            self._record_restore_span(sess, ticket, dur,
                                      sum(a.nbytes for a in slabs))
            sess.slot = slot
            sess.state = "resident"
            sess.kv_len = int(ln)
            sess.last_used = time.monotonic()
            if sess.paused is not None:
                seq = sess.paused
                sess.paused = None
                sess.current = seq
                seq.slot = slot
                self._join_decode(seq)
            elif sess.pending:
                self._begin_ingest(sess.pending.pop(0), slot,
                                   sess.kv_len)

    def _record_restore_span(self, sess: _Session, ticket: dict,
                             dur: float, nbytes: int):
        """Attribute the restore to the request that paid for it via the
        PR 8 trace plane (`llm:restore`), on whichever seq carries a
        trace context."""
        seq = sess.paused or (sess.pending[0] if sess.pending else None)
        ctx = seq.trace_ctx if seq is not None else None
        if ctx is None:
            return
        try:
            from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

            tracing_plane.record_span(
                ctx, "llm:restore", ts=ticket["wall0"], dur_s=dur,
                attrs={"session": sess.session_id, "bytes": nbytes})
        except Exception:  # noqa: BLE001 — tracing is best-effort
            pass

    def _fail_session(self, sess: _Session, exc, ticket: dict):
        """A restore failed (e.g. holder died mid-pull): fail THIS
        session's requests typed and reset the session record; every
        other slot keeps decoding — the loop never wedges."""
        from ant_ray_tpu.exceptions import KVRestoreError  # noqa: PLC0415

        self.stats["restore_failures"] += 1
        err = KVRestoreError(
            f"session {sess.session_id!r}: KV restore failed: {exc!r}",
            session_id=sess.session_id)
        seqs = ([sess.paused] if sess.paused else []) + sess.pending
        sess.paused = None
        sess.pending = []
        sess.state = "failed"
        sess.handle = None
        sess.kv_len = 0
        seq0 = seqs[0] if seqs else None
        if seq0 is not None and seq0.trace_ctx is not None:
            try:
                from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

                tracing_plane.record_span(
                    seq0.trace_ctx, "llm:restore", ts=ticket["wall0"],
                    dur_s=time.monotonic() - ticket["t0"], error=True,
                    attrs={"session": sess.session_id,
                           "error": repr(exc)})
            except Exception:  # noqa: BLE001
                pass
        for seq in seqs:
            self._fail_seq(seq, err)

    def _fail_seq(self, seq: _Seq, err):
        out = RequestOutput(
            request_id=seq.request_id, prompt_token_ids=seq.prompt,
            token_ids=list(seq.generated),
            text=self.tokenizer.decode(seq.generated),
            finished=True, finish_reason="error", error=str(err))
        self._finished.append(out)
        self._record_engine_span(seq, error=True)
        if seq.on_event is not None:
            seq.on_event({"type": "error", "error": err, "output": out})

    def _record_engine_span(self, seq: _Seq, error: bool = False):
        """The request's one stage span, ``llm:engine``, as it leaves
        the engine: ``queue`` (submit → its first prefill program
        dispatched), ``prefill`` (→ its first token handed to
        ``on_event``), ``decode`` (→ now); the step numbers join it to
        the ``engine`` steps of a device trace.  Of a sampled context
        also every token's hand-over: ``emit_ms``, one entry a token
        handed to its caller (a stop token is not), milliseconds after
        the first one's, which is the end of ``prefill``; and
        ``chunk_gaps``, the indexes ``i`` of those whose gap from token
        ``i - 1`` saw a prefill program of ANY request dispatched, and
        ``prompt_end_gaps``, those of them whose program was a prompt's
        LAST (``stats["prompt_ends"]`` rose: the gap in which a first
        token is sampled, and the row joins).  Of a model that
        generates by diffusion over blocks also ``blocks`` (stored for
        the request), ``blocks_fused`` (those of them stored by a step
        that also ran the next block) and ``block_row_steps`` (block
        steps that carried its row); a block's tokens are handed over
        together, so its
        ``emit_ms`` entries are equal.  An unsampled context records
        nothing unless ``error``."""
        ctx = seq.trace_ctx
        if ctx is None:
            return
        now, step = time.perf_counter(), self.stats["steps"]
        wall, t_submit, submit_step = seq.submitted
        t_chunk, chunk_step = seq.first_chunk or (now, step)
        t_token, token_step = seq.first_token or (now, step)
        attrs = {}
        if seq.emits is not None:
            emits = seq.emits
            attrs["emit_ms"] = [round(1000.0 * (t - t_token), 2)
                                for t, *_ in emits]
            for name, seen in (("chunk_gaps", 1), ("prompt_end_gaps", 2)):
                attrs[name] = [i for i in range(1, len(emits))
                               if emits[i][seen] != emits[i - 1][seen]]
        try:
            from ant_ray_tpu.observability import tracing_plane  # noqa: PLC0415

            tracing_plane.record_span(
                ctx, "llm:engine", ts=wall, dur_s=now - t_submit,
                stages={"queue": t_chunk - t_submit,
                        "prefill": t_token - t_chunk,
                        "decode": now - t_token},
                attrs={"prompt_tokens": len(seq.prompt),
                       "chunks": seq.chunks,
                       **({"blocks": seq.blocks,
                           "blocks_fused": seq.blocks_fused,
                           "block_row_steps": seq.block_row_steps}
                          if self._block else {}),
                       "output_tokens": len(seq.generated),
                       "slot": seq.slot, "submit_step": submit_step,
                       "first_chunk_step": chunk_step,
                       "first_token_step": token_step,
                       "last_step": step, **attrs},
                error=error)
        except Exception:  # noqa: BLE001 — tracing is best-effort
            pass

    # ----------------------------------------------------------- private

    def _after_token(self, seq: _Seq, tok: int, now: float,
                     held: int | None = None):
        """``tok`` of ``seq`` has been read: to its caller, and the end
        of the sequence where it is one.  ``now``: the landed step's
        one clock read, where its tokens are handed over.  ``held``:
        the positions the sequence holds with this token (one more than
        ``kv_len``, unless a block's landing says otherwise)."""
        seq.generated.append(tok)
        if seq.first_token is None:
            seq.first_token = (now, self.stats["steps"])
        s = seq.sampling
        eos = getattr(self.tokenizer, "eos_id",
                      getattr(self.tokenizer, "eos_token_id", None))
        stop = set(s.stop_token_ids)
        if eos is not None:
            stop.add(int(eos))
        reason = None
        if tok in stop:
            reason = "stop"
        elif len(seq.generated) >= s.max_tokens:
            reason = "length"
        elif (held or seq.kv_len + 1) >= self.max_seq:
            reason = "length"
        if reason != "stop":
            if seq.emits is not None:
                seq.emits.append((now, self.stats["chunks"],
                                  self.stats["prompt_ends"]))
            if seq.on_event is not None:
                seq.on_event({"type": "token", "token_id": tok})
        if reason is not None:
            self._release(seq, reason)

    def _release(self, seq: _Seq, reason: str):
        out_ids = (seq.generated[:-1] if reason == "stop"
                   else seq.generated)
        out = RequestOutput(
            request_id=seq.request_id,
            prompt_token_ids=seq.prompt,
            token_ids=list(out_ids),
            text=self.tokenizer.decode(out_ids),
            finished=True,
            finish_reason=reason,
        )
        self._finished.append(out)
        self._record_engine_span(seq)
        sess = seq.session
        if seq.slot >= 0:
            if self._active.pop(seq.slot, None) is not None:
                self._active_dev = None
            if sess is None:
                self._free_slots.append(seq.slot)
            else:
                # Slot stays with the session (multi-turn KV reuse).
                # The final token's K/V was never written — carry it
                # into the next turn's ingest.
                sess.kv_len = seq.kv_len
                sess.carry = list(seq.generated[-1:])
                sess.current = None
                sess.last_used = time.monotonic()
            seq.slot = -1
        elif sess is not None and sess.current is seq:
            sess.current = None
            sess.last_used = time.monotonic()
        if sess is not None and sess.pending and sess.slot >= 0 \
                and sess.current is None and sess.paused is None:
            # Next turn already queued: put it at the head of the line.
            self._waiting.insert(0, sess.pending.pop(0))
        if seq.on_event is not None:
            seq.on_event({"type": "final", "output": out})

    def _sample_one(self, seq: _Seq, logits):
        """The first token at a prompt's end, as a (1,) device array —
        the caller's one read.  The key chain is the decode batch's:
        split once, sample with the second half, the first half stays
        on ``seq.rng_key`` for the table."""
        jnp, s = self._jnp, seq.sampling
        toks, rest, _ = self._sample_jit(
            logits[None], seq.rng_key[None], self._one_active,
            jnp.asarray([s.temperature], jnp.float32),
            jnp.asarray([s.top_k], jnp.int32),
            jnp.asarray([s.top_p], jnp.float32))
        seq.rng_key = rest[0]
        return toks

    def _sample_all(self, logits):
        """Dispatch the batch sampler on the decode step's logits; the
        key table advances and the token table takes the active rows'
        tokens on the device.  No read, no eager op."""
        if self._sampling_dev is None:
            jnp = self._jnp
            temps, top_ks, top_ps = zip(*self._sampling_rows)
            self._sampling_dev = (jnp.asarray(temps, jnp.float32),
                                  jnp.asarray(top_ks, jnp.int32),
                                  jnp.asarray(top_ps, jnp.float32))
        # what rides with the tokens: a routed model's counters, or
        # a looped model's (which is not routed)
        riding = self.cache.get("routing", self.cache.get("exits"))
        if self._block:
            (sampled, self._keys, self._last, self._masked, self._closed,
             self._closing, self._block_counts) = self._sample_jit(
                logits, self._keys, self._active_dev, *self._sampling_dev,
                riding, *self._fed(), self._block_counts,
                self.cache["length"])
            return sampled
        sampled, self._keys, self._last = self._sample_jit(
            logits, self._keys, self._active_dev, *self._sampling_dev,
            riding, self._last)
        return sampled


class _LoopHandle:
    """Per-request handle returned by :meth:`EngineLoop.submit`: an
    event queue for streaming plus a wait() for the final output."""

    def __init__(self, request_id: str, step: int = 0):
        import queue as _q  # noqa: PLC0415

        self.request_id = request_id
        self.events = _q.Queue()
        # where the llm:engine span's queue stage starts
        self.submitted = (time.time(), time.perf_counter(), step)
        self._final: RequestOutput | None = None
        self._error: BaseException | None = None
        self._done = threading.Event()

    # engine-loop side ------------------------------------------------
    def _on_event(self, ev: dict):
        if ev["type"] == "final":
            self._final = ev["output"]
        elif ev["type"] == "error":
            self._error = ev["error"]
            self._final = ev.get("output")
        self.events.put(ev)
        if ev["type"] in ("final", "error"):
            self._done.set()

    def _fail(self, exc: BaseException):
        self._on_event({"type": "error", "error": exc, "output": None})

    # caller side -----------------------------------------------------
    def wait(self, timeout: float | None = None) -> RequestOutput:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished in {timeout}s")
        if self._error is not None:
            raise self._error
        return self._final

    def __iter__(self):
        """Yield events until (and including) the final/error event."""
        while True:
            ev = self.events.get()
            yield ev
            if ev["type"] in ("final", "error"):
                return


class EngineLoop:
    """Background stepper that OWNS an engine: requests are submitted
    from any thread; one loop thread interleaves chunked prefill,
    decode, and restore landing, and streams tokens to per-request
    sinks.  This replaces the old request-holds-the-engine-lock serving
    model — TTFT isolation requires concurrent requests to share steps,
    not serialize whole generations.

    The loop also publishes the serve-autoscaling load gauges
    (``art_llm_tokens_per_s``, ``art_llm_queue_depth``,
    ``art_llm_resident_sessions``) and exposes them via
    :meth:`load_signals` for controller polling."""

    METRIC_NAMES = ("art_llm_tokens_per_s", "art_llm_queue_depth",
                    "art_llm_resident_sessions")

    def __init__(self, engine: LLMEngine, *,
                 max_waiting: int | None = None,
                 deployment: str = "llm",
                 metrics_interval_s: float = 2.0,
                 idle_sleep_s: float = 0.01):
        self._engine = engine
        self._max_waiting = (max_waiting if max_waiting is not None
                             else engine._max_waiting)
        self._deployment = deployment
        self._metrics_interval = metrics_interval_s
        self._idle_sleep = idle_sleep_s
        self._inbox: list = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._tokens_per_s = 0.0
        self._last_tick = time.monotonic()
        self._last_tokens = 0
        self._gauges = None
        self._snapshot = self._loop_snapshot(engine)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="llm-engine-loop")
        self._thread.start()

    # ------------------------------------------------------- submission

    def submit(self, prompt, sampling: SamplingParams | None = None, *,
               session_id: str | None = None,
               request_id: str | None = None,
               trace_ctx=None) -> _LoopHandle:
        """Admission-gate and enqueue one request; returns its handle.

        Sheds typed BackPressureError when the engine is KV-full (no
        free slot, nothing evictable) and the waiting line is at
        ``max_waiting`` — with the retry hint derived from the measured
        chunk-drain rate."""
        eng = self._engine
        if self._max_waiting is not None:
            with self._lock:
                inbox_n = len(self._inbox)
            # Requests waiting for a SLOT (mid-prefill seqs hold theirs
            # already and don't count against the line).  List len()
            # reads are GIL-atomic, so _waiting/_free_slots stay live;
            # the SESSION-map walks (parked count, evictability) come
            # from the loop-published snapshot — iterating _sessions
            # from this thread could blow up mid-resize.  Snapshot
            # staleness costs at most a spurious/missed 429 for one
            # request, never corruption.
            snap = self._snapshot
            waiting = inbox_n + len(eng._waiting) + snap["parked"]
            if (waiting >= self._max_waiting and not eng._free_slots
                    and not snap["evictable"]):
                from ant_ray_tpu.exceptions import BackPressureError  # noqa: PLC0415

                raise BackPressureError(
                    f"llm engine at capacity: {eng.slots} KV slots "
                    f"busy, {waiting} waiting (max_waiting="
                    f"{self._max_waiting})",
                    retry_after_s=eng.retry_after_hint())
        rid = request_id or f"req-{next(eng._req_counter)}"
        handle = _LoopHandle(rid, eng.stats["steps"])
        with self._lock:
            self._inbox.append((prompt, sampling, rid, session_id,
                                trace_ctx, handle))
        self._wake.set()
        return handle

    def _call_on_loop(self, fn, timeout: float = 30.0):
        """Run ``fn(engine)`` on the loop thread and return its result
        (None on timeout).  Every mutation of the engine's session /
        slot maps must go through here — the loop thread owns them."""
        done = threading.Event()
        res = {}

        def op(eng):
            try:
                res["val"] = fn(eng)
            finally:
                done.set()

        with self._lock:
            self._inbox.append(("__op__", op, None, None, None, None))
        self._wake.set()
        done.wait(timeout)
        return res.get("val")

    def evict_session(self, session_id: str, *, force: bool = False
                      ) -> bool:
        """Thread-safe wrapper: the eviction runs on the loop thread."""
        return bool(self._call_on_loop(
            lambda eng: eng.evict_session(session_id, force=force)))

    def end_session(self, session_id: str) -> bool:
        """Thread-safe wrapper: the teardown runs on the loop thread —
        end_session frees slots and drops session records, which would
        race the stepper if called from a replica/request thread."""
        return bool(self._call_on_loop(
            lambda eng: eng.end_session(session_id)))

    # ---------------------------------------------------------- signals

    @staticmethod
    def _loop_snapshot(eng: LLMEngine) -> dict:
        """Admission/load counters as one fresh dict, published by the
        loop thread each iteration: submit() and stats() read THIS
        instead of walking the live engine structures (which the loop
        mutates concurrently — cross-thread iteration can blow up
        mid-resize).  At worst one step stale: a bounded gauge blip."""
        return {
            "parked": sum(len(s.pending) + (1 if s.paused else 0)
                          for s in eng._sessions.values()),
            "evictable": eng.has_evictable(),
            "queue_depth": eng.queue_depth(),
            "resident_sessions": eng.resident_sessions(),
        }

    def stats(self) -> dict:
        snap = self._snapshot
        return {
            "art_llm_tokens_per_s": self._tokens_per_s,
            "art_llm_queue_depth": float(snap["queue_depth"]),
            "art_llm_resident_sessions":
                float(snap["resident_sessions"]),
        }

    load_signals = stats

    def shutdown(self, timeout: float = 5.0):
        self._stop = True
        self._wake.set()
        self._thread.join(timeout)

    # ------------------------------------------------------------- loop

    def _drain_inbox(self, eng):
        with self._lock:
            items, self._inbox = self._inbox, []
        for prompt, sampling, rid, session_id, trace_ctx, handle in items:
            if prompt == "__op__":
                sampling(eng)             # an injected loop-thread op
                continue
            try:
                eng.add_request(prompt, sampling, rid, admit=False,
                                session_id=session_id,
                                on_event=handle._on_event,
                                trace_ctx=trace_ctx,
                                submitted=handle.submitted)
            except BaseException as exc:  # noqa: BLE001 — typed to caller
                handle._fail(exc)

    def _run(self):
        """Each iteration with work is one ``engine`` step of the
        recorder — drain, the engine's own phases (dispatch decode step
        N+1, with the chunk that is due among its rows; read step N,
        emit step N), housekeeping — and a stretch
        without work is one ``idle_wait`` phase, however many times the
        wait wakes.  A decode step in flight is work: the iteration
        after a batch's last dispatch reads and emits its tokens, and a
        shutdown lands it before the thread ends."""
        eng = self._engine
        rec = eng._rec
        while not self._stop:
            if self._inbox or eng.has_unfinished():
                rec.begin()
                rec.enter("drain")
                self._drain_inbox(eng)
                if eng.has_unfinished():
                    try:
                        eng.step()
                    except Exception:  # noqa: BLE001 — keep the loop alive
                        logger.exception("llm engine step failed")
                        time.sleep(0.05)
                rec.enter("housekeeping")
                self._housekeep(eng)
                rec.end()
            else:
                rec.enter("idle_wait")
                self._wake.wait(self._idle_sleep)
                self._wake.clear()
                self._housekeep(eng)
        eng._land_flight()

    def _housekeep(self, eng):
        self._snapshot = self._loop_snapshot(eng)
        now = time.monotonic()
        if now - self._last_tick >= self._metrics_interval:
            self._tick_metrics(eng, now)

    def _tick_metrics(self, eng, now: float):
        tokens = eng.stats["tokens_generated"]
        dt = max(now - self._last_tick, 1e-6)
        self._tokens_per_s = (tokens - self._last_tokens) / dt
        self._last_tokens = tokens
        self._last_tick = now
        try:
            if self._gauges is None:
                from ant_ray_tpu.util.metrics import Gauge  # noqa: PLC0415

                self._gauges = {
                    name: Gauge(name, tag_keys=("deployment",))
                    for name in self.METRIC_NAMES}
            tags = {"deployment": self._deployment}
            for name, value in self.stats().items():
                self._gauges[name].set(value, tags)
        except Exception:  # noqa: BLE001 — metrics are best-effort
            pass
