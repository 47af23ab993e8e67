"""Mixture-of-Experts: top-k routing with GShard-style dense dispatch.

Grouped one-hot dispatch keeps the einsum overhead ~O(group_size) (DESIGN.md
§6): tokens are split into groups of ``MOE_GROUP_SIZE``; per-group expert
capacity C = ceil(group * top_k * capacity_factor / E).  The dispatch/combine
einsums contract over C, so small groups keep dispatch FLOPs a few percent of
expert FLOPs while GSPMD turns the (groups, E, C, d) <-> (E, ...) resharding
into the EP all-to-all.

Expert weights carry a leading E dim sharded over the ``model`` axis (EP);
the per-expert matmul dims shard over what remains (TP inside the expert).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import BATCH, UNC, shard_hint

MOE_GROUP_SIZE = 512
CAPACITY_FACTOR = 1.25  # GShard train default; decode uses 2.0 + capacity>=top_k (drop-free)


def init_moe(key, d: int, f: int, num_experts: int, activation: str, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std_in, std_out = d ** -0.5, f ** -0.5
    p = {
        "router": jax.random.normal(k1, (d, num_experts), jnp.float32) * std_in,
        "w_up": jax.random.normal(k3, (num_experts, d, f), dtype) * std_in,
        "w_down": jax.random.normal(k4, (num_experts, f, d), dtype) * std_out,
    }
    if activation in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k2, (num_experts, d, f), dtype) * std_in
    return p


def _routing(x_flat, router_w, top_k: int, capacity: int, num_experts: int):
    """x_flat: (G, S, d) grouped tokens -> dispatch/combine tensors.

    Returns dispatch (G,S,E,C) bool-ish, combine (G,S,E,C) fp32, aux loss.
    """
    logits = (x_flat.astype(jnp.float32) @ router_w)          # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (G,S,k)
    # renormalize selected gates (Mixtral/GShard convention)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    g, s, e = logits.shape
    # position of each (token, choice) in its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)     # (G,S,k,E)
    # priority: earlier tokens first, choice 0 before choice 1
    flat = onehot.reshape(g, s * top_k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat             # (G,S*k,E)
    pos_in_expert = pos_in_expert.reshape(g, s, top_k, e)
    within_cap = pos_in_expert < capacity
    keep = onehot * within_cap                                  # (G,S,k,E)
    cap_slot = jnp.sum(pos_in_expert * keep, axis=-1)           # (G,S,k)
    slot_onehot = jax.nn.one_hot(cap_slot.astype(jnp.int32), capacity, dtype=jnp.float32)
    # (G,S,k,E) x (G,S,k,C) -> (G,S,E,C)
    dispatch = jnp.einsum("gske,gskc->gsec", keep, slot_onehot)
    combine = jnp.einsum("gske,gskc,gsk->gsec", keep, slot_onehot, gate_vals)

    # load-balancing auxiliary loss (Switch):
    density = jnp.mean(onehot.sum(axis=2), axis=1)              # (G,E) token frac
    router_prob = jnp.mean(probs, axis=1)                       # (G,E)
    aux = jnp.mean(jnp.sum(density * router_prob, axis=-1)) * (e ** 2) / top_k
    return dispatch, combine, aux


def moe(params, x, *, top_k: int, activation: str,
        capacity_factor: float = CAPACITY_FACTOR, group_size: int | None = None):
    """x: (B,S,d) -> (B,S,d), plus aux loss (returned via tuple)."""
    group_size = group_size or MOE_GROUP_SIZE
    b, s, d = x.shape
    e = params["w_up"].shape[0]
    tokens = b * s
    gsz = min(group_size, tokens)
    groups = tokens // gsz
    x_flat = x.reshape(groups, gsz, d)
    # groups shard over the DP axes; expert hidden shards over model (TP
    # inside the expert — E < model-axis size, DESIGN.md §6)
    x_flat = shard_hint(x_flat, P(BATCH, UNC, UNC))
    capacity = max(top_k, int(gsz * top_k * capacity_factor / e))

    dispatch, combine, aux = _routing(x_flat, params["router"], top_k, capacity, e)
    dispatch = dispatch.astype(x.dtype)
    expert_in = jnp.einsum("gsd,gsec->gecd", x_flat, dispatch)  # (G,E,C,d)
    expert_in = shard_hint(expert_in, P(BATCH, None, UNC, UNC))
    # merge groups for the expert matmul: (E, G*C, d) sharded E over model
    if activation in ("swiglu", "geglu"):
        act = jax.nn.silu if activation == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"])) * \
            jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"])
    else:
        act = jax.nn.gelu if activation == "gelu" else lambda z: jax.nn.relu(z) ** 2
        h = act(jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"]))
    h = shard_hint(h, P(BATCH, None, UNC, "model"))
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    expert_out = shard_hint(expert_out, P(BATCH, None, UNC, UNC))
    out = jnp.einsum("gecd,gsec->gsd", expert_out, combine.astype(x.dtype))
    return out.reshape(b, s, d), aux
