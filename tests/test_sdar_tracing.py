"""What a model that generates by diffusion over blocks leaves in the
tracing the repository has: the block counters of ``LLMEngine.stats``
add up, the ``llm:engine`` span carries ``blocks``, ``blocks_fused`` and
``block_row_steps``, the phases keep their names, the chip's probe
(``chipbench/replica_block.py``) reads the reference at test size — and
every reader the benchmark gained returns None, and raises nothing, on
an observation of a program WITHOUT the counters or the block step (the
parent's: a new reader that raised there refused PR 49)."""

import importlib

import jax
import pytest

from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm.engine import BLOCK_COUNTERS, PHASES, EngineLoop
from ant_ray_tpu.models import llama
from ant_ray_tpu.observability import tracing_plane

CFG = llama.CONFIGS["sdar-tiny"]
B = CFG.block_length
PROMPTS = ([5, 9, 17, 3, 88, 41, 12, 13, 14, 15, 16],   # 2 blocks + 3
           [44, 55, 66],                                # no whole block
           [7, 8, 9, 10, 11, 12, 13, 14])               # 2 blocks, no tail


class _NoEos:
    def encode(self, text):
        return [ord(c) % CFG.vocab_size for c in text]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(7))


def _engine(params, **kw):
    kw = {"slots": 4, "max_seq": 96, "prefill_chunk_tokens": 8,
          "tokenizer": _NoEos(), **kw}
    return LLMEngine(CFG, params, **kw)


def _spans(trace_id, name=None):
    return [s for s in tracing_plane.recorder().snapshot()
            if s["trace_id"] == trace_id
            and (name is None or s["name"] == name)]


def test_the_block_counters_add_up(params):
    eng = _engine(params)
    outs = eng.generate([list(p) for p in PROMPTS],
                        SamplingParams(max_tokens=10))
    stats = eng.stats
    assert [len(o.token_ids) for o in outs] == [10] * 3
    assert {"block_steps", "block_rows", "blocks_stored", "blocks_fused",
            *BLOCK_COUNTERS} <= set(stats)
    # greedy on seeded random weights: no draw passes the threshold, so
    # a denoise row-step fills ONE place; every row-step is a denoise
    # step — a block's store rides its successor's first — or a store
    # pass and nothing else, of which this run has none
    assert stats["block_places_confident"] == 0
    assert stats["block_rows"] == stats["block_places_filled"] \
        + stats["block_store_rows"]
    assert stats["block_store_rows"] == 0
    # a place filled is a token handed over, one cut behind max_tokens
    # in the last block, or the first of the block that the step
    # dispatched behind a request's end began; the prompt's tail fills
    # nothing
    assert stats["tokens_generated"] == 30
    assert 30 <= stats["block_places_filled"] <= 30 + 3 * (B - 1) + 3
    # tails of 3, 3 and 0 known places: 10 tokens end in the 4th, 4th
    # and 3rd block, so 3 + 3 + 2 blocks were stored for a live request,
    # each by its successor's first step; the last block's closing rows
    # ride a step dispatched for nobody
    assert stats["blocks_stored"] == stats["blocks_fused"] == 8
    # one read a step, a step's rows its active slots
    assert stats["d2h_syncs"] == stats["decode_steps"] \
        == stats["block_steps"]
    assert stats["decode_slots"] == stats["block_rows"]
    # the second prompt holds no whole block: one chunk, empty
    assert stats["chunks"] == 3 and stats["chunk_tokens"] == 16
    assert stats["prompt_ends"] == 3


def test_phases_keep_their_names_and_tile_the_loop(params):
    eng = _engine(params)
    eng.generate([list(PROMPTS[0])], SamplingParams(max_tokens=6))
    for phase in PHASES:
        assert f"phase_{phase}_s" in eng.stats
    # a block step is the decode phase; its read the fetch phase
    assert eng.stats["phase_decode_s"] > 0
    assert eng.stats["block_fetch_s"] > 0
    assert eng.stats["block_chunk_s"] == 0.0      # no first token read


def test_the_request_span_counts_blocks_and_row_steps(params):
    eng = _engine(params)
    loop = EngineLoop(eng)
    ctxs = [tracing_plane.mint(sampled=True) for _ in PROMPTS]
    try:
        handles = [loop.submit(list(p), SamplingParams(max_tokens=10),
                               trace_ctx=c)
                   for p, c in zip(PROMPTS, ctxs)]
        outs = [h.wait(120) for h in handles]
    finally:
        loop.shutdown()
    total_blocks = total_rows = 0
    for prompt, ctx, out in zip(PROMPTS, ctxs, outs):
        (span,) = _spans(ctx.trace_id, "llm:engine")
        attrs = span["attrs"]
        tail = len(prompt) % B
        assert attrs["output_tokens"] == len(out.token_ids) == 10
        assert attrs["blocks"] == (tail + 10 - 1) // B
        # a step a place; a stored block rode its successor's first
        filled = (attrs["blocks"] + 1) * B - tail
        assert attrs["block_row_steps"] == filled
        assert attrs["blocks_fused"] == attrs["blocks"]
        assert attrs["chunks"] == max(1, len(prompt) // 8)
        # a block's tokens are handed over together: equal stamps
        emit = attrs["emit_ms"]
        assert len(emit) == 10 and emit == sorted(emit)
        first = B - tail
        assert len(set(emit[:first])) == 1
        assert len(set(emit[first:first + B])) == 1
        assert emit[first] > emit[0]
        total_blocks += attrs["blocks"]
        total_rows += attrs["block_row_steps"]
    assert eng.stats["blocks_stored"] == total_blocks \
        == eng.stats["blocks_fused"]
    # the rows of the steps dispatched behind each request's end
    assert total_rows <= eng.stats["block_rows"] <= total_rows + 3


def test_a_model_without_blocks_has_no_block_counters():
    eng = LLMEngine("tiny", slots=2, max_seq=32, prefill_chunk_tokens=8)
    assert not {"block_steps", "block_rows", "blocks_stored",
                "blocks_fused", *BLOCK_COUNTERS} & set(eng.stats)
    ctx = tracing_plane.mint(sampled=True)
    eng.add_request([5, 6, 7], SamplingParams(max_tokens=2), admit=False,
                    trace_ctx=ctx)
    while eng.has_unfinished():
        eng.step()
    (span,) = _spans(ctx.trace_id, "llm:engine")
    assert "blocks" not in span["attrs"]


NEW_READERS = ["block_step_ms", "block_row_steps_per_token",
               "block_store_pct", "block_confident_pct",
               "block_step_roofline_pct"]


def _parents_observation():
    """A traced run of a program that has no block step: the decode
    program's names in the trace, its counters in ``stats``."""
    eng = LLMEngine("olmoe-tiny", slots=2, max_seq=32,
                    prefill_chunk_tokens=8)
    before = dict(eng.stats)
    eng.generate([[5, 6, 7]], SamplingParams(max_tokens=3))
    return {"traced": {"engine": dict(eng.stats), "engine_before": before,
                       "wall": 1020.0, "host_window_s": 4.0,
                       "chunk_width": 8},
            "window_wall": 1000.0,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "client": {"requests": [(3, [10.0, 10.1, 10.2])]},
            "trace": {"window_s": 4.0, "devices": [{
                "busy_s": 1.0, "program_events": [],
                "programs": {"jit__decode": {"count": 9, "total_s": 0.1},
                             "jit__sample_batch": {"count": 9,
                                                   "total_s": 0.01}}}]}}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_of_a_program_without_blocks(name):
    import json
    import os

    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    configs = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs")
    obs = _parents_observation()
    for config in ("sdar-30b-a3b", "olmoe-1b-7b", "mistral-7b"):
        with open(os.path.join(configs, config + ".json")) as f:
            assert read({**obs, "config": json.load(f)}) is None
    for less in ({}, {"traced": None}, {"trace": None, "traced": {}},
                 {**obs, "trace": None}, {**obs, "client": None}):
        assert read(less) is None


def test_the_new_readers_read_a_block_engines_counters(params):
    eng = _engine(params)
    before = dict(eng.stats)
    eng.generate([list(p) for p in PROMPTS], SamplingParams(max_tokens=10))
    obs = {"traced": {"engine": dict(eng.stats), "engine_before": before}}

    def read(name):
        return importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(obs)

    stats = eng.stats
    assert read("block_row_steps_per_token") == pytest.approx(
        stats["block_rows"] / 30)
    # 4 steps a block of 4, the blocks cut by max_tokens and the step
    # behind each request's end above it: no fifth pass a block
    assert 1.0 <= read("block_row_steps_per_token") < 1.4
    assert read("block_store_pct") == 0.0
    assert read("block_confident_pct") == 0.0
    assert read("block_step_ms") is None            # no device trace


def _tiny_spec():
    return {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "moe_intermediate_size": 32,
            "max_position_embeddings": 512, "rope_theta": 1000000,
            "rms_norm_eps": 1e-06, "num_experts": 8,
            "num_experts_per_tok": 2, "norm_topk_prob": True,
            "mlp_only_layers": [], "decoder_sparse_step": 1,
            "sliding_window": None, "use_sliding_window": False,
            "rope_scaling": None, "tie_word_embeddings": False,
            "attention_bias": False, "hidden_act": "silu",
            "generation": {"block_length": 4, "denoising_steps": 4,
                           "confidence_threshold": 0.9,
                           "remasking": "low_confidence_dynamic",
                           "mask_token_id": 255},
            "model": {"factory": "chipbench.models.sdar_moe:build"},
            "reference": {
                "module": "chipbench.reference.sdar_moe_decoder",
                "params": "chipbench.models.sdar_moe:reference_layers"},
            "serve": {"probe": {"tail_tokens": 2, "blocks": 3,
                                "short_prompt_tokens": 8}}}


def test_the_chips_probe_reads_the_reference_at_test_size():
    """``BlockProbeLLMServer.probe_logits`` on the CPU at a tiny size
    (bfloat16, as the factory builds it): 2 + 4 + 1 denoise steps of 4
    rows, each against the reference's full forward pass at ONE length —
    and a reference of the other q/k norm reads far outside."""
    from chipbench import replica_block

    spec = _tiny_spec()
    server = replica_block.BlockProbeLLMServer(
        spec, slots=3, max_seq=64, seed=4, prefill_chunk_tokens=8)
    try:
        out = server.probe_logits(7, 16, 8)
        eng = server.engine
        tokens, states = server._loop._call_on_loop(
            lambda e: replica_block.block_states(
                e, 7, 16, spec["serve"]["probe"]), timeout=300.0)
        assert [stored for stored, _, _ in states] == [16] * 2 + [20] * 4 \
            + [24]
        assert [int((fed == 255).sum()) for _, fed, _ in states] == [
            2, 1, 4, 3, 2, 1, 4]
        stored, fed, logits = states[3]
        import numpy as np

        def worst(want):
            got = np.asarray(logits, np.float64)
            want = np.asarray(want, np.float64)
            return float(np.sqrt(((got - want) ** 2).sum(-1)
                                 / (want ** 2).sum(-1)).max())

        right = worst(replica_block.reference_rows(
            spec, eng.params, tokens, stored, fed))
        wrong = worst(replica_block.reference_rows(
            spec, eng.params, tokens, stored, fed, qk_norm="whole"))
    finally:
        server.shutdown()
    assert out["positions"] == 2 * 7 * 4 and len(out["rel_l2"]) == 2
    assert len(out["rel_l2_by_position"]) == 56
    assert max(out["rel_l2"]) < 0.02 and right < 0.02 < 0.05 < wrong
    assert eng.stats["tokens_generated"] == 0       # no request was served


def test_the_parity_script_reads_the_fused_states_at_test_size():
    """``benchmarks/sdar_block_parity.py``'s fused states — block b
    closing + block b+1's first step, one step of the engine's one
    program — at the probe's tiny size: the rows of the block in flight
    read the reference (0.005), and both wrong masks read far outside
    (a closing block that sees its successor 0.06, a block blind to the
    closing one 0.24)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import sdar_block_parity
    from chipbench import replica_block

    spec = _tiny_spec()
    server = replica_block.BlockProbeLLMServer(
        spec, slots=3, max_seq=64, seed=4, prefill_chunk_tokens=8)
    try:
        out = server._loop._call_on_loop(
            lambda e: sdar_block_parity.fused_reading(
                spec, e, 7, 8, jnp, np, True), timeout=300.0)
    finally:
        server.shutdown()
    assert len(out["rows"]) == 2 * B and out["rel_l2"] < 0.02
    for name, rows in out["controls"].items():
        # as the probe reads: the median row (behind 8 stored tokens)
        assert len(rows) == 2 and np.median(rows) > 0.03, name


def test_a_block_model_streams_over_http(shutdown_only):
    """``serve.run(build_llm_deployment("sdar-tiny"))`` and ``POST
    /v1/completions`` with ``"stream": true``: the normal path, no
    option of its own — several token events behind one landing reach
    the client as a frame each, and exactly ``max_tokens`` of them."""
    import json
    import urllib.request

    import ant_ray_tpu as art
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    try:
        art.init(num_cpus=2, num_tpus=1)     # the replica leases a chip
        serve.run(build_llm_deployment("sdar-tiny", slots=2, max_seq=64),
                  port=0)
        url = f"http://127.0.0.1:{serve.run.last_http_port}/v1/completions"

        def stream(body):
            req = urllib.request.Request(
                url, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            tokens, reason = [], None
            with urllib.request.urlopen(req, timeout=180) as resp:
                for raw in resp:
                    line = raw.decode().strip()
                    if line == "data: [DONE]":
                        break
                    if line.startswith("data: "):
                        choice = json.loads(line[6:])["choices"][0]
                        if choice["finish_reason"] is None:
                            tokens.append(choice["token_id"])
                        else:
                            reason = choice["finish_reason"]
            return tokens, reason

        body = {"prompt": [5, 9, 17, 3, 88, 41], "max_tokens": 7,
                "stream": True, "temperature": 1.0, "seed": 3}
        tokens, reason = stream(body)
        assert len(tokens) == 7 and reason == "length"
        assert stream(body) == (tokens, reason)        # seeded: repeats
        # a stop token in the middle of a block: what follows is dropped
        cut, reason = stream({**body, "stop_token_ids": [tokens[4]]})
        assert reason == "stop" and cut == tokens[:tokens.index(tokens[4])]
    finally:
        if art.is_initialized():
            serve.shutdown()
