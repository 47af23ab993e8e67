"""Jit'd FDTD3d wrapper: pads, runs one stencil step (or n alternating steps)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpret
from repro.kernels.fdtd3d.kernel import BZ, fdtd3d_pallas
from repro.kernels.fdtd3d.ref import RADIUS, fdtd3d_ref


def _pad(grid):
    R = RADIUS
    return jnp.pad(grid, R, mode="edge")


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fdtd3d_step(grid, coeffs, *, use_pallas: bool = True):
    """One 8th-order stencil application. grid: (Z,Y,X), Z % 8 == 0."""
    padded = _pad(grid)
    if not use_pallas:
        return fdtd3d_ref(padded, coeffs)
    return fdtd3d_pallas(padded, coeffs, interpret=use_interpret())


@functools.partial(jax.jit, static_argnames=("steps", "use_pallas"))
def fdtd3d_run(grid, coeffs, steps: int = 4, *, use_pallas: bool = True):
    """n timesteps, output of step k feeding step k+1 (the paper's
    read/write-interleaved two-array pattern collapses to functional form)."""
    def body(g, _):
        return fdtd3d_step(g, coeffs, use_pallas=use_pallas), None

    out, _ = jax.lax.scan(body, grid, None, length=steps)
    return out
