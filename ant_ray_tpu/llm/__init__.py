"""ant_ray_tpu.llm — JAX-native LLM serving and batch inference.

Capability mirror of the reference's ``ray.llm`` (ref: python/ray/llm/
_internal/serve/engines/vllm/, deployments/, batch/stages/
vllm_engine_stage.py), re-designed TPU-first: instead of wrapping an
external CUDA engine, the engine IS the framework's own JAX model with
dense per-slot KV slabs, chunked prefill, and a continuous-batching
scheduler whose compiled step functions have static shapes.
"""

from ant_ray_tpu.llm.batch import build_llm_processor, build_logprob_processor
from ant_ray_tpu.llm.engine import LLMEngine, RequestOutput
from ant_ray_tpu.llm.sampling import SamplingParams
from ant_ray_tpu.llm.tokenizer import ByteTokenizer, get_tokenizer

__all__ = [
    "ByteTokenizer",
    "LLMEngine",
    "RequestOutput",
    "SamplingParams",
    "build_llm_processor",
    "build_logprob_processor",
    "get_tokenizer",
]
