"""Operations and HBM bytes a serving job needs, from the configuration's
shapes alone (not from the compiled program).

FLOPs count the multiply-adds of the matrix products as 2 each, and
attention as causal: a prompt of S tokens needs S*S/2 query-key pairs, not
S*S, whatever the program computes. Bytes count what a decode step must
move at least: every weight once, the keys and values of the live
positions (not of the whole preallocated cache), the new key and value,
and the logits written.
"""
from __future__ import annotations


def _dtype_bytes(cfg: dict) -> int:
    return 2 if cfg["dtype"] == "bfloat16" else 4


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    attn = 2 * d * hq * dh + 2 * d * hkv * dh
    mlp = (3 if cfg["hidden_act"] in ("swiglu", "geglu") else 2) * d * f
    return attn + mlp


def table_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def params(cfg: dict) -> int:
    """Parameters, norms included."""
    norms = 2 * cfg["hidden_size"] * (2 if cfg["norm"] == "layernorm" else 1)
    per_layer = layer_matmul_params(cfg) + norms
    tables = table_params(cfg) * (1 if cfg["tie_word_embeddings"] else 2)
    return cfg["num_hidden_layers"] * per_layer + tables + norms // 2


def weight_bytes(cfg: dict) -> int:
    return params(cfg) * _dtype_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * _dtype_bytes(cfg))


def prefill_flops(cfg: dict, batch: int, prompt_len: int) -> float:
    """One prefill call: the prompt through every layer, causal attention,
    and the logits of the last position only."""
    L = cfg["num_hidden_layers"]
    hq_dh = cfg["num_attention_heads"] * cfg["head_dim"]
    dense = 2 * prompt_len * L * layer_matmul_params(cfg)
    attn = L * 2 * prompt_len * prompt_len * hq_dh   # (QK + PV) * 2 flops * S^2/2
    logits = 2 * table_params(cfg)
    return float(batch * (dense + attn + logits))


def decode_live(prompt_len: int, gen: int) -> float:
    """Mean number of positions a decode step attends to: step i (0-based)
    feeds token i at position prompt_len + i and sees prompt_len + i + 1."""
    steps = gen - 1
    return prompt_len + 1 + (steps - 1) / 2


def decode_step_flops(cfg: dict, batch: int, live: float) -> float:
    L = cfg["num_hidden_layers"]
    hq_dh = cfg["num_attention_heads"] * cfg["head_dim"]
    dense = 2 * (L * layer_matmul_params(cfg) + table_params(cfg))
    attn = L * 4 * live * hq_dh
    return float(batch * (dense + attn))


def decode_step_bytes(cfg: dict, batch: int, live: float) -> float:
    b = _dtype_bytes(cfg)
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    weights = L * layer_matmul_params(cfg) * b + table_params(cfg) * b
    if not cfg["tie_word_embeddings"]:
        weights += batch * d * b          # the input rows of the embedding
    kv_read = batch * live * kv_bytes_per_token(cfg)
    kv_write = batch * kv_bytes_per_token(cfg)
    logits = batch * cfg["vocab_size"] * b
    return float(weights + kv_read + kv_write + logits)


def decode_bound(cfg: dict, batch: int, live: float, chips: int,
                 peak: dict) -> tuple[float, str]:
    """Least time of one decode step over ``chips`` chips, and which roofline
    binds it."""
    t_flops = decode_step_flops(cfg, batch, live) / (chips * peak["bf16_flops"])
    t_bytes = decode_step_bytes(cfg, batch, live) / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
