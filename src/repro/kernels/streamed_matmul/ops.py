"""Jit'd wrapper with automatic padding to block multiples."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import use_interpret
from repro.kernels.streamed_matmul.kernel import matmul_pallas
from repro.kernels.streamed_matmul.ref import matmul_ref


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "use_pallas"))
def matmul(a, b, *, bm: int = 256, bk: int = 512, bn: int = 256,
           use_pallas: bool = True):
    if not use_pallas:
        return matmul_ref(a, b)
    M, K = a.shape
    _, N = b.shape

    def rnd(x, m):
        return -(-x // m) * m

    bm_, bk_, bn_ = min(bm, rnd(M, 8)), min(bk, rnd(K, 128)), min(bn, rnd(N, 128))
    Mp, Kp, Np = rnd(M, bm_), rnd(K, bk_), rnd(N, bn_)
    ap = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    bp = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    out = matmul_pallas(ap, bp, bm=bm_, bk=bk_, bn=bn_,
                        interpret=use_interpret())
    return out[:M, :N]
