"""Flash attention (GQA, causal + sliding window) Pallas TPU kernel.

Kernel-level oversubscription (DESIGN.md §2): the scores of a long prefill
are S² per head, far beyond the ~16 MB of VMEM, so K/V stream through VMEM
in (block_kv, Dh) tiles with the online-softmax recurrence (running max,
exp-sum and accumulator in VMEM scratch) and no score ever reaches HBM.

Grid: (B*Hkv, Sq/block_q, Skv/block_kv). One grid step holds all G = Hq/Hkv
query heads of one KV head, so each K/V tile is fetched once per group. The
arrays keep the model's layout, heads flattened into the last dim: q and the
output (B, S, Hq*Dh), k and v (B, S, Hkv*Dh); a block is a 128-lane-aligned
column slice, so no transpose runs before or after the kernel.

Casts: both dots take their operands in the input dtype (bf16 in serving)
and accumulate in f32; max, exp and sum run in f32, and the probabilities
are cast back to the input dtype before P·V, as in the dense path.

Padding: the wrapper pads Sq and Skv to a block multiple and Dh to a lane
multiple, with zeros. Queries are the last Sq positions of the key stream,
so under the causal mask padded keys lie after every real query; without
it they are masked. Padded query rows and head dims are sliced off.

Skipped tiles: a tile beyond the causal diagonal or wholly before the
sliding window does no work (``pl.when``), and the K/V index map clamps its
block index to the nearest live tile, so the pipeline repeats a block it
already holds and issues no DMA for it. Tiles wholly inside the band skip
the mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# Block sizes: PERF.md §6 records the timings they rest on.
BLOCK_Q = 512
BLOCK_KV = 512
VMEM_LIMIT_BYTES = 64 * 2**20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_kv: int, group: int, dp: int,
               q_offset: int, skv: int, pad_keys: bool, window,
               causal: bool, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions of this tile's first and last query and key
    q_lo = qi * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_kv
    k_hi = k_lo + block_kv - 1

    live = True
    full = True        # every (query, key) pair of the tile attends
    if causal:
        live = k_lo <= q_hi
        full = k_hi <= q_lo
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)
        full = jnp.logical_and(full, k_lo > q_hi - window)
    if pad_keys:
        full = jnp.logical_and(full, k_hi < skv)

    def band_mask():
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kpos <= qpos if causal else kpos < skv
        if window is not None:
            mask = mask & (kpos > qpos - window)
        return mask

    def tile(masked: bool):
        mask = band_mask() if masked else None
        k = k_ref[0]                                   # (bkv, dp)
        v = v_ref[0]
        for h in range(group):
            cols = pl.ds(h * dp, dp)
            s = jax.lax.dot_general(
                q_ref[0, :, cols], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # (bq, bkv) f32
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]                          # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            # a row with no key yet sums exp(0) here; corr is 0 once its
            # first key comes, so that is dropped (each real row has a key)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:, cols] = acc_ref[:, cols] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    pl.when(jnp.logical_and(live, full))(lambda: tile(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(lambda: tile(True))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        for h in range(group):
            cols = pl.ds(h * dp, dp)
            inv = 1.0 / jnp.maximum(l_ref[h], 1e-20)
            o_ref[0, :, cols] = (acc_ref[:, cols] * inv).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           block_q: int = BLOCK_Q, block_kv: int = BLOCK_KV,
                           interpret: bool):
    """q: (B,Sq,Hq,Dh); k/v: (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh).

    Queries are the last Sq positions of the Skv keys (q_offset = Skv - Sq).
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    dp = _round_up(dh, LANES)
    block_q = min(block_q, _round_up(sq, LANES))
    block_kv = min(block_kv, _round_up(skv, LANES))
    sq_p, skv_p = _round_up(sq, block_q), _round_up(skv, block_kv)
    q_offset = skv - sq
    nq, nkv = sq_p // block_q, skv_p // block_kv

    def flat(x, s_p):
        x = jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, 0), (0, dp - dh)))
        return x.reshape(b, s_p, -1)

    qf, kf, vf = flat(q, sq_p), flat(k, skv_p), flat(v, skv_p)

    def q_index(bh, i, j):
        return (bh // hkv, i, bh % hkv)

    def kv_index(bh, i, j):
        # clamp skipped tiles to the nearest live one: no new DMA for them
        q_lo = i * block_q + q_offset
        if causal:
            j = jnp.minimum(j, (q_lo + block_q - 1) // block_kv)
        if window is not None:
            j = jnp.maximum(j, jnp.maximum(q_lo - window + 1, 0) // block_kv)
        return (bh // hkv, j, bh % hkv)

    kern = functools.partial(
        _fa_kernel, block_q=block_q, block_kv=block_kv, group=group, dp=dp,
        q_offset=q_offset, skv=skv, pad_keys=skv_p != skv, window=window,
        causal=causal, scale=1.0 / math.sqrt(dh),
    )
    out = pl.pallas_call(
        kern,
        grid=(b * hkv, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, group * dp), q_index),
            pl.BlockSpec((1, block_kv, dp), kv_index),
            pl.BlockSpec((1, block_kv, dp), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, group * dp), q_index),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, hq * dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 1), jnp.float32),
            pltpu.VMEM((group, block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, group * dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, sq_p, hq, dp)[:, :sq, :, :dh]
