"""Jit'd flash-attention wrapper."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import use_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_kv", "use_pallas")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = 512, block_kv: int = 512,
                    use_pallas: bool = True):
    if not use_pallas:
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=use_interpret(),
    )
