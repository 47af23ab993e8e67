"""Attention math invariants (split-KV decode, flash vs dense) + data
pipeline determinism/prefetch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection must not error (dev-only dependency)
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, prefetched, synthetic_batches
from repro.launch.step import (abstract_caches, abstract_params,
                               build_serve_step, input_specs)
from repro.models.attention import (
    attention,
    attention_flash,
    combine_decode_partials,
    decode_attention,
    decode_attention_partial,
)


HEAD_PAIRS = [(4, 4), (4, 2), (24, 2), (8, 1)]   # (Hq, Hkv): MHA, GQA, MQA


@jax.jit
def _decode_oracle(q, k, v, valid):
    """Plain float32 one-token attention: K/V repeated to every query head,
    one softmax per head over the valid positions."""
    g = q.shape[1] // k.shape[2]
    k = jnp.repeat(k, g, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, g, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k,
                   precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, v, precision="highest")


@pytest.mark.parametrize("hq,hkv", HEAD_PAIRS)
def test_grouped_decode_matches_repeated_oracle(key, hq, hkv):
    """Grouped decode attention (each KV head read once for its G query
    heads) equals attention over K/V repeated to every query head."""
    B, S, Dh = 3, 40, 16
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, hq, Dh))
    k = jax.random.normal(ks[1], (B, S, hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, hkv, Dh))
    # partly valid rows, each with at least one valid position
    valid = jax.random.bernoulli(ks[3], 0.6, (B, S)).at[:, 0].set(True)
    num, den, m = jax.jit(decode_attention_partial)(q, k, v, valid)
    assert num.shape == (B, hq, Dh) and den.shape == m.shape == (B, hq)
    assert num.dtype == den.dtype == m.dtype == jnp.float32
    out = combine_decode_partials(num, den, m, None)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_decode_oracle(q, k, v, valid)),
                               atol=1e-5)


@pytest.mark.parametrize("hq,hkv", HEAD_PAIRS)
def test_split_kv_decode_equals_full(key, hq, hkv):
    """Partial-softmax shards combine to the exact full attention (the
    flash-decoding combine used for seq-sharded KV decode)."""
    B, S, Hq, Hkv, Dh = 2, 64, hq, hkv, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    valid = jnp.ones((B, S), bool)
    num, den, m = decode_attention_partial(q, k, v, valid)
    full = combine_decode_partials(num, den, m, None)

    # shard into 4 KV chunks, combine manually with the running-max merge
    chunks = [decode_attention_partial(q, k[:, i::4], v[:, i::4],
                                       valid[:, i::4]) for i in range(4)]
    g_m = jnp.max(jnp.stack([c[2] for c in chunks]), 0)
    num_c = sum(c[0] * jnp.exp(c[2] - g_m)[..., None] for c in chunks)
    den_c = sum(c[1] * jnp.exp(c[2] - g_m) for c in chunks)
    merged = num_c / jnp.maximum(den_c[..., None], 1e-20)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), atol=1e-5)


def test_decode_attention_masks_beyond_cache_len(key):
    B, S, Hq, Hkv, Dh = 1, 32, 2, 1, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    out_short = decode_attention(q, k, v, jnp.int32(10))
    # poisoning entries >= 10 must not change the result
    k2 = k.at[:, 10:].set(99.0)
    v2 = v.at[:, 10:].set(-99.0)
    out_poison = decode_attention(q, k2, v2, jnp.int32(10))
    np.testing.assert_allclose(np.asarray(out_short), np.asarray(out_poison),
                               atol=1e-6)


@pytest.mark.parametrize("arch_name", ["starcoder2-3b", "mixtral-8x22b"])
def test_decode_step_never_repeats_kv(arch_name):
    """The lowered serve step (full cache; ring-buffered sliding window) holds
    no K/V broadcast to the query heads, (B,Smax,Hkv,G,Dh) or (B,Smax,Hq,Dh),
    only the grouped (B,Hkv,G,Smax) scores."""
    arch = get_config(arch_name)
    model = dataclasses.replace(arch.model.reduce(), num_heads=8, num_kv_heads=2)
    arch = dataclasses.replace(arch, model=model)
    B, S, G, Dh = 3, 64, 4, 16
    assert model.head_dim == Dh
    shape = ShapeConfig("d", seq_len=S, global_batch=B, kind="decode")
    caches = abstract_caches(arch, shape)
    assert caches["k"].shape[1:] == (B, S, 2, Dh)
    text = jax.jit(build_serve_step(arch)).lower(
        abstract_params(arch), input_specs(arch, shape), caches,
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert f"tensor<{B}x2x{G}x{S}xf32>" in text
    assert f"tensor<{B}x{S}x2x{G}x{Dh}x" not in text
    assert f"tensor<{B}x{S}x8x{Dh}x" not in text


@pytest.mark.parametrize("unroll", [False, True])
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.sampled_from([None, 16, 48]))
def test_flash_equals_dense_property(unroll, seed, window):
    key = jax.random.key(seed)
    B, S, Hq, Hkv, Dh = 1, 96, 2, 1, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    dense = attention(q, k, v, causal=True, window=window)
    flash = attention_flash(q, k, v, causal=True, window=window, block=32,
                            unroll=unroll)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), atol=1e-5)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic():
    cfg = get_config("qwen2-7b").model.reduce()
    shape = ShapeConfig("t", 16, 4, "train")
    a = list(zip(range(3), synthetic_batches(cfg, shape, DataConfig(seed=7))))
    b = list(zip(range(3), synthetic_batches(cfg, shape, DataConfig(seed=7))))
    for (_, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_pipeline_labels_are_shifted_tokens():
    cfg = get_config("qwen2-7b").model.reduce()
    shape = ShapeConfig("t", 16, 2, "train")
    batch = next(synthetic_batches(cfg, shape))
    np.testing.assert_array_equal(batch["labels"][:, :-1], batch["tokens"][:, 1:])


def test_prefetch_iterator_equivalence():
    cfg = get_config("qwen2-7b").model.reduce()
    shape = ShapeConfig("t", 16, 2, "train")
    plain = [next(synthetic_batches(cfg, shape)) for _ in range(1)]
    pre = prefetched(cfg, shape, depth=3)
    first = next(pre)
    np.testing.assert_array_equal(np.asarray(first["tokens"]),
                                  plain[0]["tokens"])


def test_vlm_batch_has_frontend_stub():
    cfg = get_config("qwen2-vl-2b").model.reduce()
    shape = ShapeConfig("t", 8, 2, "train")
    batch = next(synthetic_batches(cfg, shape))
    assert batch["embeds"].shape == (2, 8, cfg.d_model)
    assert batch["positions_thw"].shape == (2, 8, 3)
