"""Jitted public wrapper for the flash attention kernel (compiled on a
TPU, interpreted on the CPU: ``repro.kernels.interpret_mode``)."""
from __future__ import annotations

from repro.kernels.attention.flash_attention import flash_attention as _fa


def flash_attention(q, k, v, *, causal=True, softcap=0.0, window=0,
                    segment_ids=None, block_map=None,
                    block_q=128, block_k=128):
    return _fa(q, k, v, causal=causal, softcap=softcap, window=window,
               segment_ids=segment_ids, block_map=block_map,
               block_q=block_q, block_k=block_k)


def compile_cache_size() -> int:
    """Number of compiled flash-attention executables (tests assert this
    stays flat across pack-layout switches under a fixed bucket shape)."""
    return _fa._cache_size()
