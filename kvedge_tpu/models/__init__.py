"""Model payloads hosted by the runtime.

The reference hosts an opaque external payload (the Azure IoT Edge daemon);
kvedge-tpu's payload slot is JAX-native, and the flagship occupant is a
compact decoder-only transformer LM designed TPU-first: bf16 compute onto
the MXU, ``lax.scan`` over layers (one compiled layer body regardless of
depth), static shapes, and Megatron-style dp×tp sharding via the rules in
:mod:`kvedge_tpu.parallel.sharding`.
"""

from kvedge_tpu.models.transformer import (
    PRESETS,
    TransformerConfig,
    init_params,
    forward,
    forward_hidden,
    forward_with_aux,
    loss_fn,
    make_train_step,
    serving_params,
)
from kvedge_tpu.models.decode import (
    KVCache,
    init_cache,
    prefill,
    decode_step,
    generate,
)
from kvedge_tpu.models.kvcache import PagedKVCache, PagedCacheError
from kvedge_tpu.models.speculative import generate_speculative

__all__ = [
    "generate_speculative",
    "PRESETS",
    "TransformerConfig",
    "init_params",
    "forward",
    "forward_hidden",
    "forward_with_aux",
    "loss_fn",
    "make_train_step",
    "serving_params",
    "KVCache",
    "init_cache",
    "prefill",
    "decode_step",
    "generate",
    "PagedKVCache",
    "PagedCacheError",
]
