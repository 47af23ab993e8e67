"""Streamed matmul — the kernel-level cudaMemPrefetchAsync analogue.

K-blocked GEMM whose A/B tiles stream HBM->VMEM through the Pallas grid
pipeline: while the MXU consumes tile k, tile k+1 is being DMA'd — exactly
the double-buffered bulk prefetch the paper evaluates, one level down the
TPU memory hierarchy (DESIGN.md §2 table).  fp32 accumulation in VMEM
scratch; MXU-aligned blocks (multiples of 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a, b, *, bm: int = 256, bk: int = 512, bn: int = 256,
                  out_dtype=None, interpret: bool):
    """a: (M,K), b: (K,N); M/K/N multiples of the block sizes."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    out_dtype = out_dtype or a.dtype
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        mm_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
    )(a, b)
