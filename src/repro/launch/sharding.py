"""Sharding rules: parameter / batch / cache PartitionSpecs for every arch.

Strategy (DESIGN.md §6):
  * TP over "model": attention heads, FFN hidden, vocab, expert hidden.
  * FSDP (ZeRO-3-style) over "data": the non-TP matrix dim of every large
    parameter (XLA all-gathers at use; optimizer state stays fully sharded).
  * Pure DP over "pod": parameters replicated across pods; only gradient
    all-reduce crosses the inter-pod link.
  * SP: sequence-sharded KV caches over "model" for decode (split-KV —
    GSPMD turns the masked softmax reductions into the flash-decoding
    partial-softmax combine), ring-buffer caches for SWA archs.

Rules are keyed on parameter path + rank — a compact production pattern
(MaxText-style logical axes reduced to a name table).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def _param_rule(path: str, ndim: int, cfg: ModelConfig) -> P:
    """PartitionSpec for a *single layer's* parameter (no leading L dim)."""
    d, m = "data", "model"
    # --- embeddings / heads: vocab over model (TP), d over data (FSDP)
    if path.endswith("embedding") or path.endswith("lm_head"):
        return P(None, m, d) if ndim == 3 else P(m, d)
    # --- norms & small vectors replicate
    if "ln" in path or "norm" in path or path.endswith(("scale", "bias")):
        return P()
    if ndim == 1:
        # per-channel vectors (mus, D, dt_bias, biases): shard the channel
        # over model when it is a hidden-projection output, else replicate
        if path.endswith(("bq", "bk", "bv")):
            return P(m)
        if path.endswith(("conv_b", "dt_bias", "D", "u")):
            return P(m) if "mamba" in path else P()
        return P()
    # --- attention
    if path.endswith(("wq", "wk", "wv")):
        return P(d, m)
    if path.endswith("wo"):
        return P(m, d)
    # --- dense mlp
    if path.endswith(("w_gate", "w_up")) and "moe" not in path:
        return P(d, m)
    if path.endswith("w_down") and "moe" not in path:
        return P(m, d)
    # --- moe: experts replicated on the E dim (E < model size), TP inside
    if path.endswith("router"):
        return P(d, None)
    if "moe" in path and ndim == 3:
        if path.endswith(("w_gate", "w_up")):
            return P(None, d, m)
        return P(None, m, d)  # w_down
    # --- rwkv time/channel mix
    if path.endswith(("tm/w_r", "tm/w_k", "tm/w_v", "tm/w_g")):
        return P(d, m)
    if path.endswith("tm/w_o"):
        return P(m, d)
    if path.endswith(("cm/w_k", "cm/w_r")):
        return P(d, m)
    if path.endswith("cm/w_v"):
        return P(m, d)
    if path.endswith(("decay_A", "decay_B")):
        return P()  # tiny lora
    if path.endswith("u") and ndim == 2:
        return P()  # (H, N) bonus
    # --- mamba
    if path.endswith("in_proj"):
        return P(d, m)
    if path.endswith("out_proj"):
        return P(m, d)
    if path.endswith(("w_dt",)):
        return P(m, None)
    if path.endswith(("w_B", "w_C", "A_log")):
        return P(m, None)
    if path.endswith("conv_w"):
        return P(None, m)
    # fallback: shard the largest dim over model
    return P(*(m if i == ndim - 1 else None for i in range(ndim)))


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
    return "/".join(parts)


def param_specs(cfg: ModelConfig, params_tree) -> dict:
    """Spec tree matching the param tree (stacked layers get leading None)."""

    def rule(path, leaf):
        p = _path_str(path)
        ndim = len(leaf.shape)
        if p.startswith("layers/"):
            return P(None, *_param_rule(p, ndim - 1, cfg))
        return _param_rule(p, ndim, cfg)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def param_shardings(cfg: ModelConfig, params_tree, mesh) -> dict:
    specs = param_specs(cfg, params_tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


# ---------------------------------------------------------------------------
# Batches and caches
# ---------------------------------------------------------------------------

def _dp(mesh) -> tuple[str, ...] | str:
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _axes_size(mesh, axes) -> int:
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return size


def _dp_size(mesh) -> int:
    return _axes_size(mesh, _dp(mesh))


def batch_specs(cfg: ModelConfig, mesh, kind: str, global_batch: int | None = None) -> dict:
    """PartitionSpecs for input batches (see launch/step.py input_specs)."""
    dp = _dp(mesh)
    # drop axes (pod first) until the batch divides; unsharded as last resort
    while (isinstance(dp, tuple) and dp and global_batch is not None
           and global_batch % max(_axes_size(mesh, dp), 1) != 0):
        dp = dp[1:] or None
    if (global_batch is not None and dp is not None
            and global_batch % max(_axes_size(mesh, dp), 1) != 0):
        dp = None  # tiny batches (long_500k B=1) stay unsharded
    if kind in ("train", "prefill"):
        specs = {}
        if cfg.frontend in ("audio",) and cfg.num_codebooks > 1:
            specs["tokens"] = P(dp, None, None)
            specs["labels"] = P(dp, None, None)
        elif cfg.frontend == "vision":
            specs["embeds"] = P(dp, None, None)
            specs["labels"] = P(dp, None)
            specs["positions_thw"] = P(dp, None, None)
        else:
            specs["tokens"] = P(dp, None)
            specs["labels"] = P(dp, None)
        if kind == "prefill":
            specs.pop("labels", None)
        return specs
    # decode: one token per sequence
    if cfg.family == "audio":
        return {"tokens": P(dp, None)}
    return {"tokens": P(dp)}


def cache_specs(cfg: ModelConfig, mesh, batch: int) -> dict:
    """Decode-cache specs: sequence (or state channel) sharded over model."""
    dp = _dp(mesh)
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]  # mesh.shape: OrderedDict axis -> size
    bspec = dp if batch % max(dp_size, 1) == 0 and batch >= dp_size else None
    if cfg.family == "ssm":
        return {
            "tm_shift": P(None, bspec, "model"),
            "cm_shift": P(None, bspec, "model"),
            "wkv": P(None, bspec, None, "model", None),  # key dim N over model
        }
    specs = {
        "k": P(None, bspec, "model", None, None),   # SP: seq over model
        "v": P(None, bspec, "model", None, None),
    }
    if cfg.family == "hybrid":
        specs["conv"] = P(None, bspec, None, "model")     # d_inner over model
        specs["ssm"] = P(None, bspec, "model", None)
    return specs
