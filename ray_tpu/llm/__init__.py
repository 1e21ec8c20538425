"""ray_tpu.llm — TPU-native LLM inference: paged KV cache + continuous
batching + serve deployment.

Capability target: the reference's ray.serve.llm stack (reference:
python/ray/llm/_internal/serve/ — vLLM engine wrapper, deployment,
OpenAI-style router), rebuilt on JAX/Pallas instead of vLLM/CUDA:
ops/paged_attention.py is the ragged paged-attention kernel (prefill
chunks and decode rows in one call), ops/moe.py the dropless routed-expert
layer, llm/model.py the one step program both run in, llm/engine.py the
continuous-batching loop, llm/serve_llm.py the serve deployment.

Submodules import lazily (PEP 562): the jax-heavy engine/serve stack
only loads when its names are touched, so jax-free pieces like
``ray_tpu.llm.request_log`` stay importable in processes (and tier-1
tests) that never build an engine.
"""

_LAZY = {
    "LLMBatchPredictor": ("ray_tpu.llm.batch", "LLMBatchPredictor"),
    "batch_inference": ("ray_tpu.llm.batch", "batch_inference"),
    "PageAllocator": ("ray_tpu.llm.cache", "PageAllocator"),
    "PrefixCache": ("ray_tpu.llm.cache", "PrefixCache"),
    "make_kv_cache": ("ray_tpu.llm.cache", "make_kv_cache"),
    "InferenceEngine": ("ray_tpu.llm.engine", "InferenceEngine"),
    "LLMServer": ("ray_tpu.llm.serve_llm", "LLMServer"),
    "build_llm_app": ("ray_tpu.llm.serve_llm", "build_llm_app"),
    "placement_for_engine": ("ray_tpu.llm.serve_llm",
                             "placement_for_engine"),
    "FlightRecorder": ("ray_tpu.llm.request_log", "FlightRecorder"),
    "RequestRecord": ("ray_tpu.llm.request_log", "RequestRecord"),
}

__all__ = list(_LAZY)


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib
    value = getattr(importlib.import_module(mod_name), attr)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
