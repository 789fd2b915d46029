"""serve.llm — decode-optimized LLM inference plane.

Paged KV-cache on the device (`kv_cache.py`) + continuous-batching engine on the
AOT compile cache (`engine.py`) + a Serve deployment streaming tokens
over `handle_request_streaming` (`deployment.py`). See the README
"Inference plane" section for the engine loop and `EngineConfig`.
"""

from ray_tpu.serve.llm.kv_cache import (
    KVCacheError,
    OutOfPagesError,
    PagedKVCache,
    PrefixCache,
)
from ray_tpu.serve.llm.engine import (
    EngineConfig,
    LLMEngine,
    Request,
    RequestRejected,
)
from ray_tpu.serve.llm.deployment import LLMDeployment, build_app

__all__ = [
    "EngineConfig",
    "KVCacheError",
    "LLMDeployment",
    "LLMEngine",
    "OutOfPagesError",
    "PagedKVCache",
    "PrefixCache",
    "Request",
    "RequestRejected",
    "build_app",
]
