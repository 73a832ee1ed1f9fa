"""Mesh-parallel LLM engine + chat API (ref capability:
vllm_models.py:222 tensor_parallel_size — the engine shards itself —
and the OpenAI /v1/chat/completions surface)."""

import json
import urllib.request

import jax
import pytest

import ant_ray_tpu as art
from ant_ray_tpu.llm import LLMEngine, SamplingParams
from ant_ray_tpu.llm.chat import render_chat
from ant_ray_tpu.llm.tokenizer import ByteTokenizer
from ant_ray_tpu.models import llama


@pytest.fixture(scope="module")
def params():
    return llama.init_params(llama.CONFIGS["tiny"], jax.random.PRNGKey(7))


def test_tp_engine_builds_mesh_and_shards(params):
    engine = LLMEngine("tiny", params=params, slots=2,
                       tensor_parallel_size=2)
    assert engine.mesh is not None and engine.mesh.shape["tp"] == 2
    # wq shards its head dim over tp; the KV slab shards kv-heads.
    wq = engine.params["layers"]["wq"]   # stacked (n_layers, ...) leaf
    assert "tp" in str(wq.sharding.spec)
    assert str(engine.cache["k"].sharding.spec).count("tp") == 1


def test_tp_prefill_decode_parity(params):
    prompt = [3, 5, 7, 11, 13, 17]
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    single = LLMEngine("tiny", params=params, slots=2)
    tp2 = LLMEngine("tiny", params=params, slots=2,
                    tensor_parallel_size=2)
    out_single = single.generate([prompt], sp)[0]
    out_tp = tp2.generate([prompt], sp)[0]
    assert out_single.token_ids == out_tp.token_ids


def test_tp_must_divide_heads(params):
    with pytest.raises(ValueError, match="divide"):
        LLMEngine("tiny", params=params, slots=2,
                  tensor_parallel_size=3)  # n_heads=4, n_kv_heads=2


def test_render_chat_generic_template():
    tok = ByteTokenizer()
    ids = render_chat(tok, [{"role": "system", "content": "be brief"},
                            {"role": "user", "content": "hi"}])
    text = tok.decode(ids)
    assert "<|system|>" in text and "<|user|>" in text
    assert text.endswith("<|assistant|>\n")
    with pytest.raises(ValueError):
        render_chat(tok, [])
    with pytest.raises(ValueError):
        render_chat(tok, [{"role": "user"}])


@pytest.mark.slow
def test_chat_completions_http_e2e(shutdown_only):
    art.init(num_cpus=2, num_tpus=1)   # the replica leases a chip
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    app = build_llm_deployment("tiny", slots=2, max_seq=128)
    serve.run(app, port=0)
    port = serve.run.last_http_port

    def post(path, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=180) as resp:
            return json.loads(resp.read())

    # chat endpoint
    reply = post("/v1/chat/completions", {
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 4})["result"]
    assert reply["object"] == "chat.completion"
    msg = reply["choices"][0]["message"]
    assert msg["role"] == "assistant" and isinstance(msg["content"], str)
    assert reply["usage"]["completion_tokens"] >= 1
    # completions endpoint still served under the same /v1 prefix
    reply = post("/v1/completions", {"prompt": "hi",
                                     "max_tokens": 4})["result"]
    assert reply["object"] == "text_completion"
    serve.shutdown()


@pytest.mark.slow
def test_chat_sse_streaming(shutdown_only):
    art.init(num_cpus=2, num_tpus=1)   # the replica leases a chip
    from ant_ray_tpu import serve
    from ant_ray_tpu.llm.serve_llm import build_llm_deployment

    app = build_llm_deployment("tiny", slots=2, max_seq=128)
    serve.run(app, port=0)
    port = serve.run.last_http_port
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    chunks = []
    with urllib.request.urlopen(req, timeout=180) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            chunks.append(json.loads(payload))
    assert chunks and chunks[-1]["done"] is True
    deltas = [c for c in chunks if not c["done"]]
    assert deltas
    assert all(c["object"] == "chat.completion.chunk" for c in chunks)
    assert all("content" in c["choices"][0]["delta"] for c in deltas)
    serve.shutdown()
