"""Jit'd flash-attention wrapper: compiled on the TPU, interpreted on the
CPU (``repro.kernels.backend``)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import use_interpret
from repro.kernels.flash_attention.kernel import (
    BLOCK_KV,
    BLOCK_Q,
    flash_attention_pallas,
)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_kv")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = BLOCK_Q, block_kv: int = BLOCK_KV):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=use_interpret(),
    )
