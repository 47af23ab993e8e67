"""LM micro-benchmarks: wall time per train/decode step on reduced configs
(real CPU execution) + Pallas kernel call timings vs pure-jnp oracle."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.kernels import black_scholes, fdtd3d_step, flash_attention, matmul
from repro.kernels.black_scholes.ref import black_scholes_ref
from repro.kernels.streamed_matmul.ref import matmul_ref
from repro.models import decode_step, init_caches, init_params, loss_fn


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def arch_step_rows(archs=ARCH_NAMES) -> list[str]:
    rows = ["table,arch,op,us_per_call,derived"]
    key = jax.random.key(0)
    for name in archs:
        cfg = get_config(name).model.reduce()
        params = init_params(key, cfg)
        B, S = 2, 64
        if cfg.family == "audio":
            toks = jax.random.randint(key, (B, S, cfg.num_codebooks), 0, cfg.vocab_size)
            batch = {"tokens": toks, "labels": toks}
            dt = {"tokens": jnp.zeros((B, cfg.num_codebooks), jnp.int32)}
        elif cfg.family == "vlm":
            batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model)),
                     "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
            dt = {"tokens": jnp.zeros((B,), jnp.int32)}
        else:
            toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
            batch = {"tokens": toks, "labels": toks}
            dt = {"tokens": jnp.zeros((B,), jnp.int32)}

        train = jax.jit(jax.value_and_grad(lambda p: loss_fn(p, batch, cfg)))
        us = _time(lambda p: train(p)[0], params)
        rows.append(f"lm,{name},train_step,{us:.0f},reduced B{B}xS{S}")

        caches = init_caches(cfg, B, S)
        dec = jax.jit(lambda p, b, c: decode_step(p, b, c, jnp.int32(3), cfg)[0])
        us = _time(dec, params, dt, caches)
        rows.append(f"lm,{name},decode_step,{us:.0f},reduced B{B}")
    return rows


def kernel_rows() -> list[str]:
    rows = ["table,kernel,variant,us_per_call,derived"]
    backend = jax.devices()[0].platform  # interpret on cpu, compiled on tpu
    key = jax.random.key(0)
    n = 1 << 14
    s = jax.random.uniform(key, (n,), jnp.float32, 5, 30)
    x = jax.random.uniform(key, (n,), jnp.float32, 1, 100)
    t = jax.random.uniform(key, (n,), jnp.float32, 0.5, 5)
    rows.append(f"kernel,black_scholes,pallas_{backend},"
                f"{_time(lambda: black_scholes(s, x, t)):.0f},n={n}")
    rows.append(f"kernel,black_scholes,jnp_ref,"
                f"{_time(lambda: jax.jit(lambda: black_scholes_ref(s, x, t, 0.02, 0.3))()):.0f},n={n}")

    a = jax.random.normal(key, (256, 512), jnp.float32)
    b = jax.random.normal(key, (512, 256), jnp.float32)
    rows.append(f"kernel,streamed_matmul,pallas_{backend},"
                f"{_time(lambda: matmul(a, b)):.0f},256x512x256")
    rows.append(f"kernel,streamed_matmul,jnp_ref,"
                f"{_time(lambda: jax.jit(lambda: matmul_ref(a, b))()):.0f},256x512x256")

    q = jax.random.normal(key, (1, 256, 4, 64), jnp.float32)
    kk = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    rows.append(f"kernel,flash_attention,pallas_{backend},"
                f"{_time(lambda: flash_attention(q, kk, v, block_q=128, block_kv=128)):.0f},S=256")

    g = jax.random.normal(key, (16, 24, 136), jnp.float32)
    c = jnp.array([0.5, 0.1, 0.05, 0.02, 0.01], jnp.float32)
    rows.append(f"kernel,fdtd3d,pallas_{backend},"
                f"{_time(lambda: fdtd3d_step(g, c)):.0f},16x24x136")
    return rows
