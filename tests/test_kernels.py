"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py
oracles + hypothesis property tests (brief deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection must not error (dev-only dependency)
    from _hypothesis_fallback import given, settings, st

from repro.kernels import (
    black_scholes,
    fdtd3d_step,
    flash_attention,
    matmul,
    paged_attention,
)
from repro.kernels.black_scholes.ref import black_scholes_ref
from repro.kernels.fdtd3d.ref import fdtd3d_ref
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.streamed_matmul.ref import matmul_ref


# ---------------------------------------------------------------------------
# Black-Scholes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [7, 128, 1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_black_scholes_shapes(n, dtype, key):
    k1, k2, k3 = jax.random.split(key, 3)
    s = jax.random.uniform(k1, (n,), dtype, 5.0, 30.0)
    x = jax.random.uniform(k2, (n,), dtype, 1.0, 100.0)
    t = jax.random.uniform(k3, (n,), dtype, 0.25, 10.0)
    c, p = black_scholes(s, x, t)
    cr, pr = black_scholes_ref(s, x, t, 0.02, 0.30)
    np.testing.assert_allclose(c, cr, atol=1e-4)
    np.testing.assert_allclose(p, pr, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    spot=st.floats(1.0, 500.0), strike=st.floats(1.0, 500.0),
    t=st.floats(0.05, 20.0), r=st.floats(0.0, 0.2), v=st.floats(0.05, 1.0),
)
def test_black_scholes_properties(spot, strike, t, r, v):
    """Financial invariants: put-call parity + call in [S-Ke^-rt, S]."""
    s = jnp.full((128,), spot, jnp.float32)
    x = jnp.full((128,), strike, jnp.float32)
    tt = jnp.full((128,), t, jnp.float32)
    c, p = black_scholes(s, x, tt, r=r, v=v)
    c, p = np.asarray(c[0]), np.asarray(p[0])
    parity = c - p - (spot - strike * np.exp(-r * t))
    assert abs(parity) < 1e-2 * max(1.0, spot, strike)
    assert c >= max(0.0, spot - strike * np.exp(-r * t)) - 1e-2
    assert c <= spot + 1e-2


# ---------------------------------------------------------------------------
# Streamed matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (300, 700, 250), (256, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype, key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (m, k), dtype)
    b = jax.random.normal(k2, (k, n), dtype)
    out = matmul(a, b)
    ref = matmul_ref(a, b)
    atol = 1e-3 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=atol * np.sqrt(k), rtol=1e-2)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1), (24, 2)])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(hq, hkv, window, dtype, key):
    B, S, Dh = 2, 256, 32
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, hq, Dh), dtype)
    k = jax.random.normal(k2, (B, S, hkv, Dh), dtype)
    v = jax.random.normal(k3, (B, S, hkv, Dh), dtype)
    out = flash_attention(q, k, v, window=window, block_q=128, block_kv=128)
    ref = flash_attention_ref(q, k, v, window=window)
    atol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_cross_lengths(key):
    """Sq < Skv (continuation chunk): offsets line up with the ref."""
    B, Sq, Skv, Hq, Hkv, Dh = 1, 128, 256, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh))
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh))
    out = flash_attention(q, k, v, block_q=128, block_kv=128)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-3)


def _f32_ref(q, k, v, **kw):
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return np.asarray(flash_attention_ref(*f32, **kw))


@pytest.mark.parametrize("sq,skv", [(500, 500), (200, 500)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_pads_lengths_that_are_no_block_multiple(
        sq, skv, causal, window, key):
    """500 = 3 * 128 + 116: the wrapper pads queries and keys to the block
    and slices the padded rows off; padded keys never get weight."""
    B, Hq, Hkv, Dh = 2, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, sq, Hq, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, skv, Hkv, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, skv, Hkv, Dh), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_kv=128)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _f32_ref(q, k, v, causal=causal, window=window),
                               atol=3e-2)
    # with every value 1 the weights show: a padded key (value 0) would
    # take weight from the real ones and pull the output below 1
    ones = flash_attention(q, k, jnp.ones_like(v), causal=causal,
                           window=window, block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(ones, np.float32), 1.0, atol=5e-3)


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_at_starcoder2_3b_heads(window, dtype, key):
    """Hq 24 over Hkv 2 (G 12 query heads a grid step), Dh 128, at a
    reduced length of three blocks and a part."""
    B, S, Hq, Hkv, Dh = 1, 400, 24, 2, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    out = flash_attention(q, k, v, window=window, block_q=128, block_kv=128)
    atol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _f32_ref(q, k, v, window=window), atol=atol)


# ---------------------------------------------------------------------------
# Paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("psz,pages", [(16, 4), (32, 8)])
def test_paged_attention_sweep(psz, pages, key):
    B, Hq, Hkv, Dh = 3, 8, 2, 32
    npages = pages * B + 2
    ks = jax.random.split(key, 4)
    poolk = jax.random.normal(ks[0], (npages, psz, Hkv, Dh))
    poolv = jax.random.normal(ks[1], (npages, psz, Hkv, Dh))
    q = jax.random.normal(ks[2], (B, Hq, Dh))
    bt = jax.random.permutation(ks[3], npages)[: B * pages].reshape(B, pages)
    sl = jnp.array([psz * pages, psz * pages - 5, 3], jnp.int32)
    out = paged_attention(q, poolk, poolv, bt.astype(jnp.int32), sl)
    ref = paged_attention_ref(q, poolk, poolv, bt.astype(jnp.int32), sl)
    np.testing.assert_allclose(out, ref, atol=2e-3)


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_paged_attention_block_table_permutation(data):
    """Permuting physical pages + matching block table = same output."""
    key = jax.random.key(data.draw(st.integers(0, 2**31 - 1)))
    B, Hq, Hkv, Dh, psz, pages = 2, 4, 2, 16, 8, 4
    npages = B * pages
    ks = jax.random.split(key, 4)
    poolk = jax.random.normal(ks[0], (npages, psz, Hkv, Dh))
    poolv = jax.random.normal(ks[1], (npages, psz, Hkv, Dh))
    q = jax.random.normal(ks[2], (B, Hq, Dh))
    bt = jnp.arange(npages, dtype=jnp.int32).reshape(B, pages)
    sl = jnp.array([psz * pages, psz * pages - 3], jnp.int32)
    out1 = paged_attention(q, poolk, poolv, bt, sl)
    perm = jax.random.permutation(ks[3], npages)
    inv = jnp.argsort(perm)
    out2 = paged_attention(q, poolk[perm], poolv[perm], inv[bt], sl)
    np.testing.assert_allclose(out1, out2, atol=1e-4)


# ---------------------------------------------------------------------------
# FDTD3d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 16, 128), (16, 24, 136), (24, 8, 256)])
def test_fdtd3d_sweep(shape, key):
    g = jax.random.normal(key, shape, jnp.float32)
    coef = jnp.array([0.5, 0.1, 0.05, 0.02, 0.01], jnp.float32)
    out = fdtd3d_step(g, coef)
    ref = fdtd3d_ref(jnp.pad(g, 4, mode="edge"), coef)
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_fdtd3d_constant_field_invariant(key):
    """A constant field stays constant iff coefficients sum appropriately:
    out = c0*x + sum_r c_r*6x  => factor = c0 + 6*sum(c_r)."""
    g = jnp.full((8, 16, 128), 2.5, jnp.float32)
    coef = jnp.array([0.4, 0.05, 0.03, 0.015, 0.005], jnp.float32)
    out = fdtd3d_step(g, coef)
    factor = float(coef[0] + 6 * coef[1:].sum())
    np.testing.assert_allclose(out, 2.5 * factor, rtol=1e-5)
