"""FDTD3d Pallas TPU kernel — halo-aware VMEM tiling of a 3-D stencil.

The z dimension streams through VMEM in slabs; each grid step receives TWO
consecutive z-blocks of the padded array (block i and i+1, block size == 2R)
so the 16 rows covering [out_slab - R, out_slab + R] are resident — a
halo-exchange expressed purely through overlapping BlockSpec views, with the
grid pipeline prefetching the next slab during the current slab's VPU work
(the paper's streaming-access FDTD pattern, DESIGN.md §2).  y/x stay whole
inside the block: slices along them are static, MXU-free VPU adds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fdtd3d.ref import RADIUS

BZ = 2 * RADIUS  # z slab == halo extent so two views cover slab+halo exactly


def _fdtd_kernel(cur_ref, nxt_ref, c_ref, o_ref, *, Y: int, X: int):
    R = RADIUS
    ext = jnp.concatenate([cur_ref[...], nxt_ref[...]], axis=0).astype(jnp.float32)
    # ext rows 0..2*BZ cover padded z rows [i*BZ, i*BZ + 2*BZ); the output
    # slab needs rows [i*BZ + 0 .. i*BZ + BZ + 2R) = ext[0 : BZ + 2R) — all 16.
    c = c_ref[...].astype(jnp.float32)  # (1, R+1) in VMEM
    interior = ext[R:R + BZ, R:R + Y, R:R + X]
    out = c[0, 0] * interior
    for r in range(1, R + 1):
        out = out + c[0, r] * (
            ext[R - r:R - r + BZ, R:R + Y, R:R + X]
            + ext[R + r:R + r + BZ, R:R + Y, R:R + X]
            + ext[R:R + BZ, R - r:R - r + Y, R:R + X]
            + ext[R:R + BZ, R + r:R + r + Y, R:R + X]
            + ext[R:R + BZ, R:R + Y, R - r:R - r + X]
            + ext[R:R + BZ, R:R + Y, R + r:R + r + X]
        )
    o_ref[...] = out.astype(o_ref.dtype)


def fdtd3d_pallas(padded, coeffs, *, interpret: bool):
    """padded: (Z+2R, Y+2R, X+2R) with Z % BZ == 0; coeffs: (RADIUS+1,)."""
    R = RADIUS
    Zp, Yp, Xp = padded.shape
    Z, Y, X = Zp - 2 * R, Yp - 2 * R, Xp - 2 * R
    assert Z % BZ == 0, f"Z ({Z}) must be a multiple of {BZ}"
    nz = Z // BZ
    # views of the padded array: block i and block i+1 (z blocks of BZ);
    # padded Z has Z + 2R = (nz+1) * BZ rows exactly.
    assert Zp == (nz + 1) * BZ
    c2d = coeffs.reshape(1, R + 1)
    kern = functools.partial(_fdtd_kernel, Y=Y, X=X)
    return pl.pallas_call(
        kern,
        grid=(nz,),
        in_specs=[
            pl.BlockSpec((BZ, Yp, Xp), lambda i: (i, 0, 0)),
            pl.BlockSpec((BZ, Yp, Xp), lambda i: (i + 1, 0, 0)),
            pl.BlockSpec((1, R + 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BZ, Y, X), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Y, X), padded.dtype),
        interpret=interpret,
    )(padded, padded, c2d)
