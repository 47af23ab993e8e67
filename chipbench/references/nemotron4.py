"""Plain float32 reference for Nemotron-4: a dense GQA decoder whose rotary
position embedding turns only the first part of each head.

Covers the configurations whose file names ``"reference": "nemotron4"``.
Written from arXiv:2402.16819 (Table 1) and NeMo's ``Nemotron4Config15B``
(``rotary_percent`` 0.5, ``rotary_base`` 10000, squared ReLU without a gate,
``layernorm1p``, untied embeddings) and the configuration file alone; it
imports nothing of the program under test.

From ``dense_gqa`` it takes, by import, what the two share: the weight
scheme remade from the seed, the norm, the float8 control, the blocked
causal attention and the blocked logits, all in float32 at
``precision="highest"``. Its own is the rotary embedding: of each query and
key head only the first ``partial_rotary_factor * head_dim`` dims rotate,
rotate-half style within them, at frequencies ``theta^(-i/(rot/2))``; the
other dims pass unchanged.

Departure from the published model: ``layernorm1p`` scales by ``1 + gamma``,
where this reference (as the served model) scales by ``gamma``; both start
from the seeded initial weights, gamma 0 there and 1 here, so they compute
the same function.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import dense_gqa
from chipbench.references.dense_gqa import F32, VOCAB_BLOCK, _mm, _norm


def _rope(x, theta, rot):
    """x (S, H, Dh) at positions 0..S-1: the first ``rot`` dims of each head
    rotate (their halves as a pair), the rest pass unchanged."""
    return jnp.concatenate([dense_gqa._rope(x[..., :rot], theta), x[..., rot:]], axis=-1)


@partial(jax.jit, static_argnums=(2, 3))
def _layer(w, x, cfg_items, quant):
    """One decoder layer over one sequence x (S, d), f32."""
    cfg = dict(cfg_items)
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rot = int(cfg["partial_rotary_factor"] * dh)
    w = {k: v.astype(F32) for k, v in w.items()}
    s = x.shape[0]
    h = _norm(x, cfg["norm"], cfg["norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(s, hq, dh)
    k = _mm(h, w["wk"], quant).reshape(s, hkv, dh)
    v = _mm(h, w["wv"], quant).reshape(s, hkv, dh)
    q, k = _rope(q, cfg["rope_theta"], rot), _rope(k, cfg["rope_theta"], rot)
    x = x + _mm(dense_gqa._attention(q, k, v).reshape(s, hq * dh), w["wo"], quant)
    h = _norm(x, cfg["norm"], cfg["norm_eps"])
    u = dense_gqa._act(_mm(h, w["w_up"], quant), cfg["hidden_act"])
    return x + _mm(u, w["w_down"], quant)


def _cfg_items(cfg: dict) -> tuple:
    return dense_gqa._cfg_items(cfg) + (
        ("partial_rotary_factor", cfg["partial_rotary_factor"]),)


def logits(cfg: dict, seed: int, seqs: np.ndarray, first: int,
           quant: str | None = None) -> np.ndarray:
    """Logits of a plain forward pass at positions ``first``..S-1.

    seqs (R, S) int tokens; returns (R, S - first, vocab) float32 on the host.
    Rows run one at a time through each layer; layer weights are made once
    per layer and dropped before the next."""
    if cfg["tie_word_embeddings"]:
        raise ValueError("Nemotron-4's input and output embeddings are untied")
    items = _cfg_items(cfg)
    k_emb, layer_keys, k_head = dense_gqa._keys(seed, cfg)
    emb = dense_gqa._table(k_emb, items)
    xs = [emb[jnp.asarray(row)].astype(F32) for row in seqs]
    del emb
    for lk in layer_keys:
        w = dense_gqa._layer_weights(lk, items)
        xs = [_layer(w, x, items, quant) for x in xs]
        del w
    head = dense_gqa._table(k_head, items)
    out = []
    for x in xs:
        cols = [dense_gqa._logits_block(x[first:], head[c:c + VOCAB_BLOCK], items, quant)
                for c in range(0, cfg["vocab_size"], VOCAB_BLOCK)]
        out.append(np.asarray(jnp.concatenate(cols, axis=-1)[:, :cfg["vocab_size"]]))
    return np.stack(out)
