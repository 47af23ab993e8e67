"""Paged decode attention — block-table KV with scalar-prefetched indirection.

The page table is the TPU rendering of the paper's core object: a level of
indirection between logical sequence positions and physical KV storage
(vLLM-style).  The block table rides the scalar-prefetch path
(PrefetchScalarGridSpec) so the *index map itself* dereferences it: page j of
sequence b is DMA'd from wherever it physically lives while page j-1
computes — fault-free on-demand paging, planned instead of reactive
(DESIGN.md §2).  Pages whose positions are entirely beyond seq_len are
masked; the online-softmax carries live in VMEM scratch.

Grid: (B, pages_per_seq).  q: (B, Hq, Dh); pools: (npages, psz, Hkv, Dh).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, page_size: int, hq: int, hkv: int,
               dh: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    group = hq // hkv
    seq_len = sl_ref[b]
    page_start = j * page_size

    @pl.when(page_start < seq_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)                      # (Hq, Dh)
        k = k_ref[0].astype(jnp.float32)                      # (psz, Hkv, Dh)
        v = v_ref[0].astype(jnp.float32)
        qg = q.reshape(hkv, group, dh)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        ) * scale                                              # (Hkv, group, psz)
        s = s.reshape(hq, page_size)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, (hq, page_size), 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                    # (Hq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)           # (Hq, psz)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(hkv, group, page_size)
        pv = jax.lax.dot_general(
            pg, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )                                                      # (Hkv, group, Dh)
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(hq, dh)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def paged_attention_pallas(q, kv_pool_k, kv_pool_v, block_table, seq_lens,
                           *, interpret: bool):
    b, hq, dh = q.shape
    npages, psz, hkv, _ = kv_pool_k.shape
    pages_per_seq = block_table.shape[1]
    kern = functools.partial(
        _pa_kernel, page_size=psz, hq=hq, hkv=hkv, dh=dh,
        scale=1.0 / math.sqrt(dh),
    )

    def page_index(bidx, j, bt_ref, sl_ref):
        # dereference the block table inside the index map: physical page id
        return (bt_ref[bidx, j], 0, 0, 0)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pages_per_seq),
            in_specs=[
                pl.BlockSpec((1, hq, dh), lambda bi, j, bt, sl: (bi, 0, 0)),
                pl.BlockSpec((1, psz, hkv, dh), page_index),
                pl.BlockSpec((1, psz, hkv, dh), page_index),
            ],
            out_specs=pl.BlockSpec((1, hq, dh), lambda bi, j, bt, sl: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
        interpret=interpret,
    )(block_table, seq_lens, q, kv_pool_k, kv_pool_v)
