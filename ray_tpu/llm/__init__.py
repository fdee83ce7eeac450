"""ray_tpu.llm — LLM batch inference + serving on the ray_tpu runtime.

TPU-native counterpart of ray.llm (ref: python/ray/llm/): the engine is
not vLLM but owned — a jit-compiled prefill + decode over the native
Llama implementation (static shapes, batched MXU matmuls), with a
continuous-batching paged-KV engine for serving.

- generation: prefill/decode_step/generate with left-padded ragged batches
- engine: ContinuousBatchingEngine — slots, pages, decode-step admission,
  token streaming; no device program of its own
- programs: the seam (ServePrograms by the config's type); llama, mla_moe:
  each family's jitted programs and cache over its layer in models/
- serving: LLMServer (@serve.batch coalescing) and LLMEngineServer
  (continuous batching + streaming) deployments
- batch: build_llm_processor over ray_tpu.data datasets
- disagg: disaggregated serving — prefill/decode pools over the KV-page
  plane with cross-request prefix caching (DisaggLLMServer)
"""
from ray_tpu.llm.batch import build_llm_processor
from ray_tpu.llm.disagg import (
    DecodeWorker,
    DisaggLLMServer,
    KVPageManifest,
    PrefillWorker,
    PrefixCache,
    build_disagg_deployment,
    prefix_hint,
)
from ray_tpu.llm.engine import ContinuousBatchingEngine, EngineFull
from ray_tpu.llm.generation import generate, generate_tokens, pad_prompts
from ray_tpu.llm.serving import (
    LLMEngineServer,
    LLMServer,
    build_llm_deployment,
    build_llm_engine_deployment,
)

__all__ = [
    "ContinuousBatchingEngine",
    "DecodeWorker",
    "DisaggLLMServer",
    "EngineFull",
    "KVPageManifest",
    "LLMEngineServer",
    "LLMServer",
    "PrefillWorker",
    "PrefixCache",
    "build_disagg_deployment",
    "build_llm_deployment",
    "build_llm_engine_deployment",
    "build_llm_processor",
    "generate",
    "generate_tokens",
    "pad_prompts",
]
