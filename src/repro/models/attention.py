"""Attention: GQA with causal / sliding-window masks; prefill and decode.

The XLA (pure-jnp) paths live here. Which one a layer runs is decided by
the placement of its input (``transformer.attn_sublayer``): a prefill whose
sequences each lie whole on one device (no mesh, or a ``model`` axis of
size 1) runs in the Pallas flash kernel (``repro.kernels.flash_attention``);
a prefill whose mesh splits the sequence over ``model`` runs ``attention``,
or ``attention_flash`` above 4096² query-key pairs, which GSPMD shards;
training runs ``attention``. The kernel tests check the Pallas kernels
against these functions.

Decode uses *split-KV* (flash-decoding style): when the KV cache is sharded
over the ``model`` mesh axis along the sequence dimension, each shard
computes a partial softmax (max, exp-sum, weighted values) and the partials
combine with one small all-reduce — this is both the sequence-parallelism
story for 32k/500k decode and the solution to GQA kv_heads < model-axis size
(DESIGN.md §6).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def causal_mask(q_len: int, kv_len: int, *, window: int | None = None, q_offset=0):
    """(q_len, kv_len) bool mask; True = attend."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (kj > qi - window)
    return m


def _attention_dense(q, k, v, *, causal, window, q_offset, mask, scale):
    """Grouped-GQA dense attention: K/V are never repeated to the query
    heads — scores are computed per kv-head group: (B, Hkv, G, Sq, Skv)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if causal:
        m = causal_mask(sq, k.shape[1], window=window, q_offset=q_offset)
        s = jnp.where(m[None, None, None], s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, sq, hq, dh)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              q_offset=0, mask=None, softmax_scale: float | None = None):
    """q: (B,Sq,Hq,Dh), k/v: (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh). fp32 softmax.

    Dense (materialized-score) path: training, where sequence parallelism
    bounds the per-device score block and the VJP is efficient under remat,
    and a sequence-split prefill up to 4096² query-key pairs (above it,
    attention_flash). A prefill on one device runs the Pallas flash kernel.
    """
    dh = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dh)
    return _attention_dense(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, mask=mask, scale=scale)


FLASH_BLOCK = 1024


def attention_flash(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset=0, softmax_scale: float | None = None,
                    block: int = FLASH_BLOCK, unroll: bool = False):
    """Memory-bounded online-softmax attention (forward only: a prefill
    whose mesh splits the sequence, above 4096² query-key pairs; training
    uses the dense path whose VJP is efficient under remat).

    Streams KV in blocks with running (max, sum, acc) — the XLA-level
    rendering of kernels/flash_attention, grouped GQA; it scores in f32.
    ``unroll=True`` loops over the KV blocks in Python and skips out-of-band
    blocks statically, matching the Pallas kernel's pl.when (the dry-run
    cost probes: XLA cost analysis counts a scan body once).
    """
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    skv = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dh)
    block = min(block, skv)
    nb = -(-skv // block)
    pad = nb * block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)

    def block_update(carry, j, kj, vj):
        m, l, acc = carry                       # (B,Hkv,G,Sq), same, (B,Sq,Hkv,G,Dh)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj.astype(jnp.float32)) * scale
        kpos = j * block + jnp.arange(block)
        mask = kpos[None, :] < skv
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)               # (B,Hkv,G,Sq)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), vj)
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv.astype(jnp.float32)
        return (m_new, l, acc)

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, sq, hkv, g, dh), jnp.float32)
    if unroll:
        carry = (m0, l0, a0)
        for j in range(nb):
            lo, hi = j * block, (j + 1) * block
            if causal and lo > int(q_offset) + sq - 1:
                continue  # static skip above the diagonal
            if window is not None and hi - 1 <= int(q_offset) - window:
                continue  # static skip before the window
            kj = jax.lax.slice_in_dim(k, lo, hi, axis=1)
            vj = jax.lax.slice_in_dim(v, lo, hi, axis=1)
            carry = block_update(carry, j, kj, vj)
        m, l, acc = carry
    else:
        ks = k.reshape(b, nb, block, hkv, dh).transpose(1, 0, 2, 3, 4)
        vs = v.reshape(b, nb, block, hkv, dh).transpose(1, 0, 2, 3, 4)

        def body(carry, xs):
            j, kj, vj = xs
            return block_update(carry, j, kj, vj), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (jnp.arange(nb), ks, vs))
    l = jnp.maximum(l, 1e-20).transpose(0, 3, 1, 2)[..., None]
    return (acc / l).reshape(b, sq, hq, dh).astype(q.dtype)


def decode_attention_partial(q, k, v, valid_mask, softmax_scale: float | None = None):
    """One-token query against a *shard* of the KV cache.

    q: (B,Hq,Dh); k/v: (B,Skv,Hkv,Dh); valid_mask: (B,Skv) bool.
    Returns partials (numerator (B,Hq,Dh) fp32, denominator (B,Hq) fp32,
    running max (B,Hq) fp32) that combine exactly across shards.

    Query heads are grouped per KV head, G = Hq // Hkv, so each KV head is
    read once for its G query heads; K/V are never repeated to Hq heads.
    """
    b, hq, dh = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, hq // hkv, dh)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k).astype(jnp.float32) * scale
    mask = valid_mask[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # (B,Hkv,G)
    p = jnp.exp(logits - m[..., None])                 # (B,Hkv,G,Skv)
    p = jnp.where(mask, p, 0.0)
    denom = jnp.sum(p, axis=-1)                        # (B,Hkv,G)
    num = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v).astype(jnp.float32)
    return num.reshape(b, hq, dh), denom.reshape(b, hq), m.reshape(b, hq)


def combine_decode_partials(num, denom, m, axis_name: str | None):
    """Combine split-KV partials over a mesh axis (flash-decoding combine)."""
    if axis_name is None:
        out = num / jnp.maximum(denom[..., None], 1e-20)
        return out
    g_m = jax.lax.pmax(m, axis_name)                   # (B,Hq)
    corr = jnp.exp(m - g_m)
    num = num * corr[..., None]
    denom = denom * corr
    num = jax.lax.psum(num, axis_name)
    denom = jax.lax.psum(denom, axis_name)
    return num / jnp.maximum(denom[..., None], 1e-20)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int | None = None,
                     axis_name: str | None = None, seq_offset=0):
    """Single-step decode attention.

    q: (B,Hq,Dh); caches: (B,Smax,Hkv,Dh) — possibly a sequence shard when
    called under shard_map (then ``seq_offset`` is the shard's global start
    and ``axis_name`` the mesh axis to combine over).
    cache_len: scalar int32 — number of valid tokens globally.
    """
    b, smax = k_cache.shape[0], k_cache.shape[1]
    pos = jnp.arange(smax)[None, :] + seq_offset        # global positions
    valid = pos < cache_len
    if window is not None:
        valid = valid & (pos > cache_len - 1 - window)
    num, denom, m = decode_attention_partial(q, k_cache, v_cache, valid)
    out = combine_decode_partials(num, denom, m, axis_name)
    return out.astype(q.dtype)


def update_kv_cache(k_cache, v_cache, k_new, v_new, cache_len):
    """Insert one token's K/V at position cache_len. Caches (B,Smax,Hkv,Dh),
    new (B,1,Hkv,Dh) or (B,Hkv,Dh)."""
    if k_new.ndim == 3:
        k_new, v_new = k_new[:, None], v_new[:, None]
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), cache_len, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), cache_len, axis=1)
    return k_cache, v_cache
