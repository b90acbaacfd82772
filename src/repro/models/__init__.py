"""Model zoo: 10 assigned architectures over a shared block-program core."""

from .transformer import (
    backbone_forward,
    decode_step,
    decode_step_paged,
    forward,
    init_cache,
    init_model,
    paged_decode_supported,
    prefill,
    prefill_chunk,
    prefill_chunk_supported,
)

__all__ = [
    "backbone_forward",
    "decode_step",
    "decode_step_paged",
    "forward",
    "init_cache",
    "init_model",
    "paged_decode_supported",
    "prefill",
    "prefill_chunk",
    "prefill_chunk_supported",
]
