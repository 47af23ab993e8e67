"""Plain float32 reference for a dense GQA decoder with rotary positions.

Covers the configurations whose file names ``"reference": "dense_gqa"``: a
token embedding, then per layer a pre-norm attention block (grouped-query,
causal, rotary on the first and second halves of each head) and a pre-norm
feed-forward block, a final norm and an output projection (tied to the
embedding or not). Written from the published description and the
configuration file alone; it imports nothing of the program under test.

Weights are remade from the seed here, by the same scheme the served model
states (normal draws in the served dtype, scaled by fan-in), one layer at a
time, so that a model larger than a chip's free memory never sits whole on
one device. Every matrix product runs at ``precision="highest"``: on a TPU a
float32 product otherwise runs in one bfloat16 pass.

``quant="fp8"`` is the control: the same forward pass with every weight
matrix and every matrix input rounded to float8 e4m3 (per output channel and
per token), the precision one step below the bfloat16 the model is served in.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 1024       # query rows per attention block
VOCAB_BLOCK = 32768  # output columns per logits block
FP8_MAX = 448.0      # largest finite float8 e4m3fn


def served_dtype(cfg: dict):
    return jnp.bfloat16 if cfg["dtype"] == "bfloat16" else F32


def _keys(seed: int, cfg: dict):
    k_emb, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    return k_emb, jax.random.split(k_layers, cfg["num_hidden_layers"]), k_head


def _normal(key, shape, std, dtype):
    return jax.random.normal(key, shape, dtype) * std


@partial(jax.jit, static_argnums=(1,))
def _layer_weights(layer_key, cfg_items):
    """One layer's matrices in the served dtype. They are widened to f32 only
    in ``_layer``: widened here, XLA may keep the draws' excess precision and
    skip the rounding to the served dtype."""
    cfg = dict(cfg_items)
    dt = served_dtype(cfg)
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, f = cfg["head_dim"], cfg["intermediate_size"]
    k_attn, k_mlp, _, _ = jax.random.split(layer_key, 4)
    ka = jax.random.split(k_attn, 4)
    w = {
        "wq": _normal(ka[0], (d, hq * dh), d ** -0.5, dt),
        "wk": _normal(ka[1], (d, hkv * dh), d ** -0.5, dt),
        "wv": _normal(ka[2], (d, hkv * dh), d ** -0.5, dt),
        "wo": _normal(ka[3], (hq * dh, d), (hq * dh) ** -0.5, dt),
    }
    km = jax.random.split(k_mlp, 3)
    if cfg["hidden_act"] in ("swiglu", "geglu"):
        w["w_gate"] = _normal(km[0], (d, f), d ** -0.5, dt)
        w["w_up"] = _normal(km[1], (d, f), d ** -0.5, dt)
        w["w_down"] = _normal(km[2], (f, d), f ** -0.5, dt)
    else:
        w["w_up"] = _normal(km[0], (d, f), d ** -0.5, dt)
        w["w_down"] = _normal(km[1], (f, d), f ** -0.5, dt)
    return w


@partial(jax.jit, static_argnums=(1,))
def _table(key, cfg_items):
    """The (vocab, hidden) embedding or output table in the served dtype."""
    cfg = dict(cfg_items)
    v = -(-cfg["vocab_size"] // 256) * 256
    return _normal(key, (v, cfg["hidden_size"]), 0.02, served_dtype(cfg))


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in f32, or with both rounded to fp8."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _act(x, kind):
    if kind == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "squared_relu":
        return jnp.square(jnp.maximum(x, 0.0))
    raise ValueError(f"activation {kind!r} has no reference here")


def _rope(x, theta):
    """x (S, H, Dh) at positions 0..S-1; halves rotated as a pair."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal grouped-query attention for one sequence, in query blocks.
    q (S, Hq, Dh), k/v (S, Hkv, Dh) -> (S, Hq, Dh)."""
    s, hq, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    nb = -(-s // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0)))
    qb = qp.reshape(nb, Q_BLOCK, hkv, g, dh)
    kpos = jnp.arange(s)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HIGHEST) / math.sqrt(dh)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(nb * Q_BLOCK, hq, dh)[:s]


@partial(jax.jit, static_argnums=(2, 3))
def _layer(w, x, cfg_items, quant):
    """One decoder layer over one sequence x (S, d), f32."""
    cfg = dict(cfg_items)
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    w = {k: v.astype(F32) for k, v in w.items()}
    s = x.shape[0]
    h = _norm(x, cfg["norm"], cfg["norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(s, hq, dh)
    k = _mm(h, w["wk"], quant).reshape(s, hkv, dh)
    v = _mm(h, w["wv"], quant).reshape(s, hkv, dh)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    x = x + _mm(_attention(q, k, v).reshape(s, hq * dh), w["wo"], quant)
    h = _norm(x, cfg["norm"], cfg["norm_eps"])
    if "w_gate" in w:
        act = jax.nn.silu if cfg["hidden_act"] == "swiglu" else partial(_act, kind="gelu_tanh")
        u = act(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    else:
        u = _act(_mm(h, w["w_up"], quant), cfg["hidden_act"])
    return x + _mm(u, w["w_down"], quant)


@partial(jax.jit, static_argnums=(2, 3))
def _logits_block(x, table_block, cfg_items, quant):
    cfg = dict(cfg_items)
    h = _norm(x, cfg["norm"], cfg["norm_eps"])
    return _mm(h, table_block.astype(F32).T, quant)


def _cfg_items(cfg: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
            "hidden_act", "norm", "norm_eps", "rope_theta", "dtype")
    return tuple((k, cfg[k]) for k in keys)


def logits(cfg: dict, seed: int, seqs: np.ndarray, first: int,
           quant: str | None = None) -> np.ndarray:
    """Logits of a plain forward pass at positions ``first``..S-1.

    seqs (R, S) int tokens; returns (R, S - first, vocab) float32 on the host.
    Rows run one at a time through each layer; layer weights are made once
    per layer and dropped before the next."""
    items = _cfg_items(cfg)
    k_emb, layer_keys, k_head = _keys(seed, cfg)
    emb = _table(k_emb, items)
    xs = [emb[jnp.asarray(row)].astype(F32) for row in seqs]
    for lk in layer_keys:
        w = _layer_weights(lk, items)
        xs = [_layer(w, x, items, quant) for x in xs]
        del w
    head = emb if cfg["tie_word_embeddings"] else _table(k_head, items)
    del emb
    out = []
    for x in xs:
        cols = [_logits_block(x[first:], head[c:c + VOCAB_BLOCK], items, quant)
                for c in range(0, cfg["vocab_size"], VOCAB_BLOCK)]
        out.append(np.asarray(jnp.concatenate(cols, axis=-1)[:, :cfg["vocab_size"]]))
    return np.stack(out)
