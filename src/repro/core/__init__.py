"""The paper's primary contribution as a composable feature set:
static-KV-cache serving engine, decoding strategies (incl. beam reorder),
LayerSkip self-speculative decoding and AutoQuant."""
from repro.core import (  # noqa: F401
    engine,
    kv_cache,
    layerskip,
    quantization,
    sampling,
)
