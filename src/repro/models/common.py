"""Shared model components: norms, rotary embeddings (RoPE / M-RoPE),
activations, embedding/unembedding.  Pure-jnp, shard-friendly (no explicit
collectives; GSPMD handles distribution from the in/out shardings).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

UNC = P.UNCONSTRAINED


BATCH = "__batch__"  # sentinel: replaced by the DP axes of the context mesh
SEQ = "__seq__"      # sentinel: "model" (sequence parallelism on the
                     # residual stream; TP over model, FSDP over data)


def batch_axes_from_ctx() -> tuple[str, ...]:
    names = set(jax.sharding.get_abstract_mesh().axis_names)
    return tuple(a for a in ("pod", "data") if a in names)


def shard_hint(x, spec: P):
    """with_sharding_constraint that degrades to a no-op when no mesh (or a
    mesh without the named axes) is in context — model code stays mesh-free;
    the launcher activates the hints with jax.set_mesh (DESIGN.md §6 SP).

    The BATCH sentinel resolves to the mesh's DP axes: UNCONSTRAINED dims are
    a GSPMD *choice*, and it will happily replicate a batch dim — batch
    sharding must be pinned explicitly."""
    names = set(jax.sharding.get_abstract_mesh().axis_names)
    if not names:
        return x
    resolved = []
    for e in spec:
        if e == BATCH:
            dp = batch_axes_from_ctx()
            resolved.append(dp if dp else None)
            continue
        if e == SEQ:
            resolved.append("model")
            continue
        resolved.append(e)
    needed = set()
    for e in resolved:
        if e is None or e is UNC:
            continue
        for n in (e if isinstance(e, tuple) else (e,)):
            needed.add(n)
    if not needed or not needed <= names:
        return x
    return jax.lax.with_sharding_constraint(x, P(*resolved))

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def apply_norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


def init_norm(d: int, kind: str, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "squared_relu": squared_relu,
    "relu": jax.nn.relu,
}


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """positions: (..., S) int -> cos/sin (..., S, head_dim/2) fp32."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """x: (B, S, H, Dh); cos/sin: (B, S, Dh/2) -> rotate half (GPT-NeoX style)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(q, k, positions, theta: float, rot: int | None = None):
    """Standard RoPE. positions: (B, S). With ``rot`` below the head size
    only the first ``rot`` dims of each head rotate, at frequencies
    theta^(-i/(rot/2)), and the rest pass unchanged (partial rotary)."""
    dh = q.shape[-1]
    rot = dh if rot is None else rot
    cos, sin = rope_angles(positions, rot, theta)
    if rot == dh:
        return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)

    def partial(x):
        return jnp.concatenate([apply_rotary(x[..., :rot], cos, sin), x[..., rot:]],
                               axis=-1)
    return partial(q), partial(k)


# M-RoPE (Qwen2-VL, arXiv:2409.12191): the head_dim is split into three
# sections rotated by the temporal / height / width position streams.
MROPE_SECTION_FRACTIONS = (0.25, 0.375, 0.375)  # (t, h, w) — 16/24/24 of 64 half-dims


def apply_mrope(q, k, positions_thw, theta: float):
    """positions_thw: (B, S, 3) int32 — (t, h, w) coordinate streams."""
    half = q.shape[-1] // 2
    sizes = [int(round(f * half)) for f in MROPE_SECTION_FRACTIONS]
    sizes[-1] = half - sizes[0] - sizes[1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # build per-frequency positions by section
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sizes)]
    )  # (half,) — which of (t,h,w) drives each frequency slot
    pos = positions_thw.astype(jnp.float32)[..., sec_id]  # (B,S,half)
    ang = pos * inv_freq[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)


def text_mrope_positions(positions):
    """For pure-text tokens all three M-RoPE streams equal the text position."""
    return jnp.stack([positions] * 3, axis=-1)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, num_codebooks: int, dtype):
    shape = (num_codebooks, vocab, d) if num_codebooks > 1 else (vocab, d)
    return jax.random.normal(key, shape, dtype) * 0.02


def embed_tokens(emb, tokens):
    """tokens: (B,S) or (B,S,K) for multi-codebook audio."""
    if emb.ndim == 3:  # (K, V, d): sum of per-codebook embeddings (MusicGen)
        if tokens.ndim == 3:  # (B,S,K)
            gathered = jax.vmap(
                lambda e, t: jnp.take(e, t, axis=0), in_axes=(0, 2), out_axes=2
            )(emb, tokens)  # (B,S,K,d)
            return jnp.sum(gathered, axis=2)
        return jnp.take(emb[0], tokens, axis=0)
    return jnp.take(emb, tokens, axis=0)


def unembed(x, emb_or_head):
    """x: (B,S,d) -> logits (B,S,V) or (B,S,K,V) for multi-codebook."""
    w = emb_or_head
    if w.ndim == 3:  # (K, V, d)
        return jnp.einsum("bsd,kvd->bskv", x, w)
    return jnp.einsum("bsd,vd->bsv", x, w)


def cross_entropy_loss(logits, labels, ignore_id: int = -1):
    """Mean next-token NLL; labels: (B,S) or (B,S,K).

    Vocab-parallel form (Megatron-style): nll = logsumexp(z) - z[label],
    expressed as reductions over the (possibly model-sharded) vocab dim —
    no take_along_axis gather and no materialized log_softmax, so GSPMD
    keeps the logits vocab-sharded and combines with two tiny psums."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_iota = jnp.arange(v, dtype=labels.dtype)
    onehot = (labels[..., None] == vocab_iota)
    tgt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - tgt
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
