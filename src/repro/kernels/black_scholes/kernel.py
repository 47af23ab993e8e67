"""Black-Scholes Pallas TPU kernel (paper app BS) — VPU-bound elementwise.

The arrays stream HBM->VMEM in (block_rows, 128) tiles through the grid
pipeline (the kernel-level analogue of bulk prefetch: block k+1 is DMA'd
while block k computes).  fp32 math on the VPU; erf-based normal CDF.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _ncdf(x):
    return 0.5 * (1.0 + jax.lax.erf(x * 0.7071067811865475))


def bs_kernel(s_ref, x_ref, t_ref, call_ref, put_ref, *, r: float, v: float):
    s = s_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    sqrt_t = jnp.sqrt(t)
    d1 = (jnp.log(s / x) + (r + 0.5 * v * v) * t) / (v * sqrt_t)
    d2 = d1 - v * sqrt_t
    disc = jnp.exp(-r * t)
    call = s * _ncdf(d1) - x * disc * _ncdf(d2)
    put = x * disc * _ncdf(-d2) - s * _ncdf(-d1)
    call_ref[...] = call.astype(call_ref.dtype)
    put_ref[...] = put.astype(put_ref.dtype)


def black_scholes_pallas(s, x, t, r: float, v: float, *,
                         block_rows: int = 256, interpret: bool):
    """s/x/t: 2-D (rows, LANE-multiple cols) arrays, same shape/dtype."""
    rows, cols = s.shape
    assert cols % LANE == 0, f"cols must be multiple of {LANE}"
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    import functools

    kern = functools.partial(bs_kernel, r=r, v=v)
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    call, put = pl.pallas_call(
        kern,
        grid=(rows // br,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype)] * 2,
        interpret=interpret,
    )(s, x, t)
    return call, put
