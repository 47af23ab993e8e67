"""Composable decoder model covering all ten assigned architectures.

Families:
  dense / moe / audio / vlm -> TransformerBlock (GQA attn + MLP or MoE)
  ssm                       -> RWKV6 block (repro.models.rwkv)
  hybrid                    -> Hymba block: parallel attention + Mamba heads

Layers are *stacked* (leading L dim) and traversed with jax.lax.scan so the
dry-run compiles one layer body regardless of depth; remat policy wraps the
scan body.  Three entry points:

  loss_fn(params, batch)                     training loss (next-token NLL)
  prefill(params, batch)                     logits + KV/recurrent caches
  decode_step(params, token_batch, caches)   one-token serve step

Caches are pytrees with a leading L dim, scanned together with the layer
weights.  Sliding-window archs use ring-buffer KV caches of window size —
this is what makes mixtral-8x22b's long_500k cell sub-quadratic (DESIGN §5).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.streaming import checkpoint_layer
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from jax.sharding import PartitionSpec as P

from repro.models.common import (
    BATCH,
    SEQ,
    UNC,
    apply_mrope,
    apply_norm,
    apply_rope,
    cross_entropy_loss,
    embed_tokens,
    init_embedding,
    init_norm,
    shard_hint,
    text_mrope_positions,
    unembed,
)


def residual_hint(cfg: ModelConfig) -> P:
    """Residual-stream sharding between layers (DESIGN.md §6):
    sequence parallelism over the model axis for attention families;
    channel TP for rwkv (the time recurrence cannot scan a sharded seq)."""
    if cfg.family == "ssm":
        return P(BATCH, UNC, SEQ)
    return P(BATCH, SEQ, UNC)
from repro.models.mlp import init_mlp, mlp


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype):
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * dh), dtype) * std,
        "wk": jax.random.normal(ks[1], (d, hkv * dh), dtype) * std,
        "wv": jax.random.normal(ks[2], (d, hkv * dh), dtype) * std,
        "wo": jax.random.normal(ks[3], (hq * dh, d), dtype) * ((hq * dh) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def init_layer(key, cfg: ModelConfig):
    dtype = _dtype(cfg)
    if cfg.family == "ssm":
        return rwkv_lib.init_rwkv_layer(key, cfg, dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": init_norm(cfg.d_model, cfg.norm, dtype),
        "attn": init_attn(k1, cfg, dtype),
        "ln2": init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if cfg.num_experts:
        p["moe"] = moe_lib.init_moe(k2, cfg.d_model, cfg.d_ff, cfg.num_experts,
                                    cfg.activation, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    if cfg.family == "hybrid":
        d_inner = cfg.num_heads * cfg.head_dim
        p["mamba"] = ssm_lib.init_ssm(k3, cfg.d_model, d_inner, cfg.ssm_state, dtype)
        p["attn_out_norm"] = init_norm(cfg.d_model, "rmsnorm", dtype)
        p["ssm_out_norm"] = init_norm(cfg.d_model, "rmsnorm", dtype)
    return p


def init_params(key, cfg: ModelConfig):
    dtype = _dtype(cfg)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embedding": init_embedding(k_emb, cfg.padded_vocab, cfg.d_model,
                                    cfg.num_codebooks, dtype),
        "layers": layers,
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(k_head, cfg.padded_vocab, cfg.d_model,
                                           cfg.num_codebooks, dtype)
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct param tree for the dry-run (no allocation)."""
    return jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))


# ---------------------------------------------------------------------------
# Blocks — full-sequence (train / prefill) path
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg: ModelConfig):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _position_embed(q, k, positions, cfg: ModelConfig):
    """Rotary positions on q and k (B, S, H, Dh), as the config states."""
    if cfg.rope == "rope":
        return apply_rope(q, k, positions, cfg.rope_theta, cfg.rotary_dim)
    if cfg.rope == "mrope":
        return apply_mrope(q, k, positions, cfg.rope_theta)
    return q, k


def _splits_sequence() -> bool:
    """Whether the context mesh shards the sequence: SEQ resolves to the
    ``model`` axis, so a mesh without one, or with one of size 1, holds each
    sequence whole on a device."""
    return jax.sharding.get_abstract_mesh().shape.get("model", 1) > 1


def attn_sublayer(p, x, cfg: ModelConfig, positions, *, return_kv=False,
                  mode: str = "train", unroll: bool = False):
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg)
    q, k = _position_embed(q, k, positions, cfg)
    # SP attention: q stays sequence-sharded; K/V replicate along seq so the
    # score matrix shards on the query dim for any head count (GQA kv=2..24)
    q = shard_hint(q, P(BATCH, SEQ, UNC, UNC))
    k = shard_hint(k, P(BATCH, None, UNC, UNC))
    v = shard_hint(v, P(BATCH, None, UNC, UNC))
    # a sequence whole on one device: the Pallas kernel keeps the scores in
    # VMEM; a sequence split over the mesh: XLA paths that GSPMD shards
    if mode == "prefill" and not _splits_sequence():
        out = flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    elif mode == "prefill" and S * k.shape[1] > 4096 * 4096:
        out = attn_lib.attention_flash(q, k, v, causal=True,
                                       window=cfg.sliding_window, unroll=unroll)
    else:
        out = attn_lib.attention(q, k, v, causal=True, window=cfg.sliding_window)
    out = out.reshape(B, S, -1) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def transformer_block(p, x, cfg: ModelConfig, positions, *, return_kv=False,
                      mode: str = "train", unroll: bool = False):
    h = apply_norm(x, p["ln1"], cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        res = attn_sublayer(p["attn"], h, cfg, positions, return_kv=return_kv,
                            mode=mode, unroll=unroll)
        a_out, kv = res if return_kv else (res, None)
        m_out, _ = ssm_lib.mamba(p["mamba"], h)
        y = 0.5 * (
            apply_norm(a_out, p["attn_out_norm"], "rmsnorm")
            + apply_norm(m_out, p["ssm_out_norm"], "rmsnorm")
        )
    else:
        res = attn_sublayer(p["attn"], h, cfg, positions, return_kv=return_kv,
                            mode=mode, unroll=unroll)
        y, kv = res if return_kv else (res, None)
    x = x + y
    h = apply_norm(x, p["ln2"], cfg.norm)
    if cfg.num_experts:
        y, aux = moe_lib.moe(p["moe"], h, top_k=cfg.top_k, activation=cfg.activation)
    else:
        y = mlp(p["mlp"], h, cfg.activation)
    x = x + y
    return (x, aux, kv) if return_kv else (x, aux)


def _scan_layers(body, carry, layers, unroll: bool):
    """scan over stacked layers; ``unroll=True`` runs a Python loop instead
    (used by the dry-run cost probes: XLA cost_analysis counts a while body
    once, so probes compile unrolled L=1/L=2 models and extrapolate)."""
    if not unroll:
        return jax.lax.scan(body, carry, layers)
    n = jax.tree.leaves(layers)[0].shape[0]
    ys = []
    for i in range(n):
        lw = jax.tree.map(lambda a: a[i], layers)
        carry, y = body(carry, lw)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def backbone(params, x, cfg: ModelConfig, positions, *, remat: str = "none",
             unroll: bool = False):
    """Full-sequence pass over all layers (scan). x: (B,S,d) embeddings."""

    hint = residual_hint(cfg)
    x = shard_hint(x, hint)
    if cfg.family == "ssm":
        def body(carry, lw):
            h, aux = carry
            h, _ = rwkv_lib.rwkv_block(lw, h, cfg, state=None)
            return (shard_hint(h, hint), aux), None
    else:
        def body(carry, lw):
            h, aux = carry
            h, a = transformer_block(lw, h, cfg, positions)
            return (shard_hint(h, hint), aux + a), None

    body = checkpoint_layer(body, remat)
    (x, aux), _ = _scan_layers(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"], unroll)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    return x, aux


def embed_inputs(params, batch, cfg: ModelConfig):
    """Embed tokens, or pass through stub-frontend embeddings (audio/vlm)."""
    if "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        x = embed_tokens(params["embedding"], batch["tokens"])
    B, S = x.shape[0], x.shape[1]
    if cfg.rope == "mrope":
        positions = batch.get("positions_thw")
        if positions is None:
            positions = text_mrope_positions(
                jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            )
    else:
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return x, positions


def logits_fn(params, x, cfg: ModelConfig):
    w = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(x, w)
    if cfg.padded_vocab != cfg.vocab_size:  # mask TP-padding columns
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.float32(-1e30).astype(logits.dtype), logits)
    # vocab-parallel logits: keep V sharded over model through the loss
    if logits.ndim == 4:  # (B,S,K,V) multi-codebook
        return shard_hint(logits, P(BATCH, UNC, None, "model"))
    return shard_hint(logits, P(BATCH, UNC, "model"))


def loss_fn(params, batch, cfg: ModelConfig, *, remat: str = "full",
            unroll: bool = False):
    """Next-token loss. batch: tokens (B,S) [or (B,S,K) audio; embeds for
    vlm/audio stubs] + labels; aux MoE loss folded in."""
    x, positions = embed_inputs(params, batch, cfg)
    x, aux = backbone(params, x, cfg, positions, remat=remat, unroll=unroll)
    logits = logits_fn(params, x, cfg)
    loss = cross_entropy_loss(logits, batch["labels"])
    if cfg.num_experts:
        loss = loss + 0.01 * aux / cfg.num_layers
    return loss


# ---------------------------------------------------------------------------
# Serving: prefill + decode with stacked caches
# ---------------------------------------------------------------------------

def cache_seq_len(cfg: ModelConfig, max_seq: int) -> int:
    if cfg.sliding_window is not None:
        return min(max_seq, cfg.sliding_window)
    return max_seq


def init_caches(cfg: ModelConfig, batch: int, max_seq: int):
    """Stacked (leading L) caches for decoding."""
    dtype = _dtype(cfg)
    L = cfg.num_layers
    if cfg.family == "ssm":
        d = cfg.d_model
        n = rwkv_lib.head_size(cfg)
        h = rwkv_lib.num_wkv_heads(cfg)
        return {
            "tm_shift": jnp.zeros((L, batch, d), dtype),
            "cm_shift": jnp.zeros((L, batch, d), dtype),
            "wkv": jnp.zeros((L, batch, h, n, n), jnp.float32),
        }
    S = cache_seq_len(cfg, max_seq)
    caches = {
        "k": jnp.zeros((L, batch, S, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((L, batch, S, cfg.num_kv_heads, cfg.head_dim), dtype),
    }
    if cfg.family == "hybrid":
        d_inner = cfg.num_heads * cfg.head_dim
        caches["conv"] = jnp.zeros((L, batch, ssm_lib.CONV_K - 1, d_inner), dtype)
        caches["ssm"] = jnp.zeros((L, batch, d_inner, cfg.ssm_state), jnp.float32)
    return caches


def _decode_attn(p, h, cfg: ModelConfig, cache, cache_len, positions):
    """One-token attention against a (possibly ring-buffered) cache.

    h: (B,1,d); cache: {"k","v"} (B,Scache,Hkv,Dh). Returns (out, new cache).
    """
    B = h.shape[0]
    q, k_new, v_new = _project_qkv(p, h, cfg)
    q, k_new = _position_embed(q, k_new, positions, cfg)
    S_cache = cache["k"].shape[1]
    if cfg.sliding_window is not None and S_cache == cfg.sliding_window:
        slot = jnp.mod(cache_len, S_cache)
    else:
        slot = jnp.minimum(cache_len, S_cache - 1)
    k_c, v_c = attn_lib.update_kv_cache(cache["k"], cache["v"], k_new, v_new, slot)
    # keep the cache SEQUENCE-sharded through the attention math (split-KV):
    # GSPMD otherwise reshards to (padded) kv-head sharding per layer — an
    # involuntary full rematerialization of the cache slice per step
    k_c = shard_hint(k_c, P(BATCH, "model", UNC, UNC))
    v_c = shard_hint(v_c, P(BATCH, "model", UNC, UNC))
    n_valid = cache_len + 1
    if cfg.sliding_window is not None and S_cache == cfg.sliding_window:
        valid = (jnp.arange(S_cache)[None, :] < n_valid) | (n_valid >= S_cache)
        valid = jnp.broadcast_to(valid, (B, S_cache))
        num, den, m = attn_lib.decode_attention_partial(q[:, 0], k_c, v_c, valid)
        out = attn_lib.combine_decode_partials(num, den, m, None).astype(h.dtype)
    else:
        out = attn_lib.decode_attention(q[:, 0], k_c, v_c, n_valid)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"k": k_c, "v": v_c}


def decode_block(p, h, cfg: ModelConfig, cache, cache_len, positions):
    """One layer, one token. h: (B,1,d)."""
    x = h
    hn = apply_norm(x, p["ln1"], cfg.norm)
    new_cache = dict(cache)
    if cfg.family == "hybrid":
        a_out, kv = _decode_attn(p["attn"], hn, cfg,
                                 {"k": cache["k"], "v": cache["v"]}, cache_len, positions)
        m_out, (conv_s, ssm_s) = ssm_lib.mamba(
            p["mamba"], hn, state=(cache["conv"], cache["ssm"])
        )
        y = 0.5 * (
            apply_norm(a_out, p["attn_out_norm"], "rmsnorm")
            + apply_norm(m_out, p["ssm_out_norm"], "rmsnorm")
        )
        new_cache.update(kv)
        new_cache["conv"], new_cache["ssm"] = conv_s, ssm_s
    else:
        y, kv = _decode_attn(p["attn"], hn, cfg,
                             {"k": cache["k"], "v": cache["v"]}, cache_len, positions)
        new_cache.update(kv)
    x = x + y
    hn = apply_norm(x, p["ln2"], cfg.norm)
    if cfg.num_experts:
        y, _ = moe_lib.moe(p["moe"], hn, top_k=cfg.top_k, activation=cfg.activation,
                           capacity_factor=2.0, group_size=hn.shape[0])
    else:
        y = mlp(p["mlp"], hn, cfg.activation)
    return x + y, new_cache


def decode_step(params, batch, caches, cache_len, cfg: ModelConfig,
                unroll: bool = False):
    """One serve step: batch["tokens"]: (B,) [or (B,K)] -> logits + caches.

    cache_len: scalar int32 — tokens already in the cache (KV cache of
    seq_len, one new token; the decode_32k/long_500k shapes).
    """
    if cfg.family in ("audio",) and batch["tokens"].ndim == 2:
        tokens = batch["tokens"][:, None, :]       # (B,1,K)
    else:
        tokens = batch["tokens"][:, None]          # (B,1)
    if "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))    # (B,1,d) stub frontends
    else:
        x = embed_tokens(params["embedding"], tokens)
    B = x.shape[0]
    if cfg.rope == "mrope":
        positions = jnp.broadcast_to(cache_len[None, None], (B, 1))
        positions = text_mrope_positions(positions)
    else:
        positions = jnp.broadcast_to(cache_len[None, None], (B, 1))

    # caches ride in the scan CARRY with per-layer in-place updates
    # (dynamic_update_index_in_dim): passing them as scan xs/ys would hold
    # TWO full KV stacks live (ys cannot alias xs through a while loop) —
    # 2x the decode working set at 32k/500k contexts.
    def write_layer(caches, new_cache, i):
        return jax.tree.map(
            lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                c, nc.astype(c.dtype), i, 0),
            caches, new_cache)

    if cfg.family == "ssm":
        def body(carry, lw):
            h, caches, i = carry
            state = tuple(
                jax.lax.dynamic_index_in_dim(caches[k], i, 0, keepdims=False)
                for k in ("tm_shift", "cm_shift", "wkv"))
            h, (tm_s, cm_s, wkv_s) = rwkv_lib.rwkv_block(lw, h, cfg, state=state)
            caches = write_layer(
                caches, {"tm_shift": tm_s, "cm_shift": cm_s, "wkv": wkv_s}, i)
            return (h, caches, i + 1), None

        (x, new_caches, _), _ = _scan_layers(
            body, (x, caches, jnp.int32(0)), params["layers"], unroll)
    else:
        def body(carry, lw):
            h, caches, i = carry
            cache_i = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
                caches)
            h, new_cache = decode_block(lw, h, cfg, cache_i, cache_len, positions)
            caches = write_layer(caches, new_cache, i)
            return (h, caches, i + 1), None

        (x, new_caches, _), _ = _scan_layers(
            body, (x, caches, jnp.int32(0)), params["layers"], unroll)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, x, cfg)[:, 0]
    return logits, new_caches


def prefill(params, batch, cfg: ModelConfig, unroll: bool = False):
    """Full-sequence forward returning last-position logits + filled caches.
    ``unroll`` loops over the layers and the flash KV blocks in Python."""
    x, positions = embed_inputs(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]

    if cfg.family == "ssm":
        def body(h, lw):
            h, state = rwkv_lib.rwkv_block(lw, h, cfg, state=None)
            return h, state

        x, states = _scan_layers(body, x, params["layers"], unroll)
        caches = {"tm_shift": states[0], "cm_shift": states[1], "wkv": states[2]}
    else:
        S_cache = cache_seq_len(cfg, S)

        hint = residual_hint(cfg)

        def body(carry, lw):
            h, aux = carry
            if cfg.family == "hybrid":
                hn = apply_norm(h, lw["ln1"], cfg.norm)
                a_out, kv = attn_sublayer(lw["attn"], hn, cfg, positions,
                                          return_kv=True, mode="prefill",
                                          unroll=unroll)
                m_out, mstate = ssm_lib.mamba(lw["mamba"], hn)
                y = 0.5 * (
                    apply_norm(a_out, lw["attn_out_norm"], "rmsnorm")
                    + apply_norm(m_out, lw["ssm_out_norm"], "rmsnorm")
                )
                h = h + y
                hn = apply_norm(h, lw["ln2"], cfg.norm)
                h = h + mlp(lw["mlp"], hn, cfg.activation)
                h = shard_hint(h, hint)
                k, v = kv
                cache = {
                    "k": k[:, -S_cache:], "v": v[:, -S_cache:],
                    "conv": mstate[0], "ssm": mstate[1],
                }
                return (h, aux), cache
            h, aux2, kv = transformer_block(lw, h, cfg, positions,
                                            return_kv=True, mode="prefill",
                                            unroll=unroll)
            h = shard_hint(h, hint)
            k, v = kv
            return (h, aux + aux2), {"k": k[:, -S_cache:], "v": v[:, -S_cache:]}

        (x, _), caches = _scan_layers(body, (x, jnp.zeros((), jnp.float32)),
                                      params["layers"], unroll)
    x = apply_norm(x, params["final_norm"], cfg.norm)
    logits = logits_fn(params, x[:, -1:], cfg)[:, 0]
    return logits, caches
