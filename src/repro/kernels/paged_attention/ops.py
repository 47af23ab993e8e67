"""Jit'd paged-attention wrapper + host-tier page pool management."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import use_interpret
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def paged_attention(q, kv_pool_k, kv_pool_v, block_table, seq_lens,
                    *, use_pallas: bool = True):
    """Decode attention over a paged KV pool.

    q: (B,Hq,Dh); pools: (npages, page_size, Hkv, Dh);
    block_table: (B, pages_per_seq) int32 physical page ids;
    seq_lens: (B,) int32 valid token counts.
    """
    if not use_pallas:
        return paged_attention_ref(q, kv_pool_k, kv_pool_v, block_table, seq_lens)
    return paged_attention_pallas(
        q, kv_pool_k, kv_pool_v, block_table, seq_lens,
        interpret=use_interpret(),
    )
