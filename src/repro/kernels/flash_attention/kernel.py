"""Flash attention (GQA, causal + sliding window) Pallas TPU kernel.

Kernel-level oversubscription (DESIGN.md §2): the KV working set for a 32 k
prefill is hundreds of MB — far beyond the ~16 MB VMEM — so K/V stream
through VMEM in (block_kv, Dh) tiles with the online-softmax recurrence
(running max / exp-sum / accumulator in VMEM scratch), while the grid
pipeline prefetches tile j+1 during tile j's MXU work.

Grid: (B*Hq, Sq/block_q, Skv/block_kv); KV blocks map to the GQA kv-head of
each query head.  Out-of-band blocks (beyond the causal diagonal or the
sliding window) are skipped with pl.when — no FLOPs, no DMA stalls.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_kv: int, sq: int, skv: int,
               window, causal: bool, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_offset = skv - sq  # queries are the last sq positions of the kv stream

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global coordinates of this tile
    q_lo = qi * block_q + q_offset
    k_lo = kj * block_kv

    def in_band():
        q = q_ref[0].astype(jnp.float32)              # (bq, dh)
        k = k_ref[0].astype(jnp.float32)              # (bkv, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # (bq, bkv)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kpos <= qpos if causal else kpos >= 0
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bkv)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)               # (bkv, dh)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    # causal: skip blocks entirely above the diagonal; window: skip blocks
    # entirely before the window of this q tile's last row.
    live = True
    if causal:
        live = k_lo <= q_lo + block_q - 1
    if window is not None:
        live = jnp.logical_and(live, k_lo + block_kv - 1 > q_lo - window)
    pl.when(live)(in_band)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool):
    """q: (B,Sq,Hq,Dh); k/v: (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh)."""
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0

    # (B*Hq, Sq, Dh) query layout; KV stays (B*Hkv, Skv, Dh)
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dh)

    def kv_index(bh, i, j):
        return ((bh // hq) * hkv + (bh % hq) // group, j, 0)

    kern = functools.partial(
        _fa_kernel, block_q=block_q, block_kv=block_kv, sq=sq, skv=skv,
        window=window, causal=causal, scale=1.0 / math.sqrt(dh),
    )
    out = pl.pallas_call(
        kern,
        grid=(b * hq, sq // block_q, skv // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, dh), kv_index),
            pl.BlockSpec((1, block_kv, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)
