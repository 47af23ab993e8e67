"""Jit'd public wrapper: flattens/pads to TPU-friendly 2-D tiles."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpret
from repro.kernels.black_scholes.kernel import LANE, black_scholes_pallas
from repro.kernels.black_scholes.ref import black_scholes_ref


@functools.partial(jax.jit, static_argnames=("r", "v", "use_pallas"))
def black_scholes(s, x, t, *, r: float = 0.02, v: float = 0.30,
                  use_pallas: bool = True):
    """Price European options. Arbitrary-shape inputs."""
    if not use_pallas:
        return black_scholes_ref(s, x, t, r, v)
    shape = s.shape
    n = s.size
    cols = LANE
    rows = -(-n // cols)
    # pad rows to a block multiple with benign values (strike=spot=t=1)
    block = min(256, rows)
    rows_p = -(-rows // block) * block
    pad = rows_p * cols - n

    def prep(a):
        flat = jnp.concatenate([a.reshape(-1), jnp.ones((pad,), a.dtype)])
        return flat.reshape(rows_p, cols)

    call, put = black_scholes_pallas(
        prep(s), prep(x), prep(t), r, v, block_rows=block,
        interpret=use_interpret(),
    )
    return call.reshape(-1)[:n].reshape(shape), put.reshape(-1)[:n].reshape(shape)
