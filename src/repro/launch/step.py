"""Step builders: sharded train_step / prefill_step / serve_step per cell,
plus ``input_specs()`` — ShapeDtypeStruct stand-ins for every model input
(weak-type-correct, shardable, no device allocation).

The modality frontends are STUBS per the brief: ``[audio]`` gets token
codebook grids shaped like EnCodec output; ``[vlm]`` gets precomputed patch
embeddings + (t,h,w) M-RoPE position streams.

The ResidencyPlan threads through here: remat policy, int8 moments, host
placement of optimizer state (memory kinds on TPU; analytic accounting on
CPU — placement.py probes the backend).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.residency import ResidencyPlan
from repro.core.advise import MemorySpace
from repro.core.streaming import fetch_params, offload_params
from repro.models import transformer as tf
from repro.optim import (
    AdamWConfig,
    apply_updates,
    clip_by_global_norm,
    init_state,
    warmup_cosine,
)
from repro.launch.sharding import batch_specs, cache_specs, param_specs


# ---------------------------------------------------------------------------
# Abstract inputs (the dry-run's ShapeDtypeStructs)
# ---------------------------------------------------------------------------

def input_specs(arch: ArchConfig, shape: ShapeConfig) -> dict:
    cfg = arch.model
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    sds = jax.ShapeDtypeStruct

    if shape.kind in ("train", "prefill"):
        if cfg.family == "audio":
            batch = {
                "tokens": sds((B, S, cfg.num_codebooks), i32),
                "labels": sds((B, S, cfg.num_codebooks), i32),
            }
        elif cfg.family == "vlm":
            batch = {
                "embeds": sds((B, S, cfg.d_model), bf16),    # stub frontend
                "labels": sds((B, S), i32),
                "positions_thw": sds((B, S, 3), i32),
            }
        else:
            batch = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if shape.kind == "prefill":
            batch.pop("labels", None)
        return batch

    # decode: KV cache of seq_len, one new token
    if cfg.family == "audio":
        return {"tokens": sds((B, cfg.num_codebooks), i32)}
    return {"tokens": sds((B,), i32)}


def abstract_caches(arch: ArchConfig, shape: ShapeConfig):
    return jax.eval_shape(
        lambda: tf.init_caches(arch.model, shape.global_batch, shape.seq_len)
    )


def abstract_params(arch: ArchConfig):
    return tf.abstract_params(arch.model)


def abstract_opt_state(arch: ArchConfig, plan: ResidencyPlan | None = None):
    cfg = _adamw_cfg(arch, plan)
    return jax.eval_shape(lambda p: init_state(p, cfg), abstract_params(arch))


def _adamw_cfg(arch: ArchConfig, plan: ResidencyPlan | None) -> AdamWConfig:
    int8 = plan.int8_moments if plan is not None else arch.train.int8_moments
    return AdamWConfig(
        weight_decay=arch.train.weight_decay,
        int8_moments=int8,
        master_dtype=arch.train.master_dtype,
    )


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------

def make_shardings(arch: ArchConfig, shape: ShapeConfig, mesh,
                   plan: ResidencyPlan | None = None):
    """NamedShardings for (params, opt_state, batch, caches)."""
    cfg = arch.model
    params = abstract_params(arch)
    pspecs = param_specs(cfg, params)
    ns = lambda spec: NamedSharding(mesh, spec)
    params_sh = jax.tree.map(ns, pspecs)

    opt_sh = None
    if shape.kind == "train":
        # None = backend-default memory; name a kind only when the plan
        # demands host placement AND the backend can compile it.
        opt_kind = None
        if plan is not None and plan.opt_space is MemorySpace.HOST:
            from repro.core.placement import backend_supports_memory_kinds
            if backend_supports_memory_kinds():
                opt_kind = "pinned_host"

        abs_opt = abstract_opt_state(arch, plan)

        # moments/master mirror the param spec; scalars replicate
        def mirror(spec, leaf_dict):
            out = {}
            for k, v in leaf_dict.items():
                if len(v.shape) == 0:
                    out[k] = NamedSharding(mesh, P(), memory_kind=opt_kind)
                elif len(v.shape) != len(spec):
                    # rank mismatch: int8 per-layer scales (L,) — replicate
                    out[k] = NamedSharding(mesh, P(*([None] * len(v.shape))),
                                           memory_kind=opt_kind)
                else:
                    out[k] = NamedSharding(mesh, spec, memory_kind=opt_kind)
            return out

        leaves_sh = jax.tree.map(
            mirror, pspecs, abs_opt["leaves"],
            is_leaf=lambda x: isinstance(x, P) or (
                isinstance(x, dict) and "master" in x
            ),
        )
        opt_sh = {"step": NamedSharding(mesh, P()), "leaves": leaves_sh}

    bspecs = batch_specs(cfg, mesh, shape.kind, shape.global_batch)
    batch_sh = {k: ns(v) for k, v in bspecs.items()}

    caches_sh = None
    if shape.kind == "decode":
        cspecs = cache_specs(cfg, mesh, shape.global_batch)
        abs_caches = abstract_caches(arch, shape)
        caches_sh = {k: ns(cspecs[k]) for k in abs_caches}
    return params_sh, opt_sh, batch_sh, caches_sh


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def build_train_step(arch: ArchConfig, shape: ShapeConfig, mesh,
                     plan: ResidencyPlan | None = None, *,
                     unroll: bool = False, total_steps: int = 10_000):
    """Returns (train_step, shardings).  train_step(params, opt, batch, step)
    -> (params, opt, metrics).  Microbatched gradient accumulation; grads in
    fp32; donation-ready."""
    cfg = arch.model
    acfg = _adamw_cfg(arch, plan)
    remat = plan.remat if plan is not None else arch.train.remat
    micro = max(1, min(arch.train.microbatches, shape.global_batch))
    opt_on_host = plan is not None and plan.opt_space is MemorySpace.HOST

    def loss(p, mb):
        return tf.loss_fn(p, mb, cfg, remat=remat, unroll=unroll)

    def train_step(params, opt_state, batch, step):
        lr = warmup_cosine(step, peak_lr=arch.train.learning_rate,
                           warmup_steps=arch.train.warmup_steps,
                           total_steps=total_steps)
        if micro == 1:
            l, grads = jax.value_and_grad(loss)(params, batch)
        else:
            mb_batch = jax.tree.map(
                lambda x: x.reshape((micro, x.shape[0] // micro) + x.shape[1:]),
                batch,
            )
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def acc(carry, mb):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l), None

            if unroll:
                g_acc, l_acc = zeros, 0.0
                for i in range(micro):
                    mb = jax.tree.map(lambda x: x[i], mb_batch)
                    (g_acc, l_acc), _ = acc((g_acc, l_acc), mb)
                grads, l = g_acc, l_acc
            else:
                (grads, l), _ = jax.lax.scan(acc, (zeros, 0.0), mb_batch)
            grads = jax.tree.map(lambda g: g / micro, grads)
            l = l / micro

        grads, gnorm = clip_by_global_norm(grads, arch.train.grad_clip)
        if opt_on_host:
            opt_state = fetch_params(opt_state, mesh)       # host -> HBM
        params, opt_state = apply_updates(params, grads, opt_state, acfg, lr)
        if opt_on_host:
            opt_state = offload_params(opt_state, mesh)     # HBM -> host
        metrics = {"loss": l, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def build_prefill_step(arch: ArchConfig, *, unroll: bool = False):
    """Prompt pass: (params, batch) -> (greedy next tokens, last-position
    logits, caches of the prompt's length)."""
    cfg = arch.model

    def prefill_step(params, batch):
        logits, caches = tf.prefill(params, batch, cfg, unroll=unroll)
        next_tokens = jnp.argmax(logits, axis=-1)
        return next_tokens, logits, caches

    return prefill_step


def build_serve_step(arch: ArchConfig, *, unroll: bool = False):
    """One-token decode step: greedy sample + cache update.
    (params, batch, caches, cache_len) -> (next tokens, logits, caches)."""
    cfg = arch.model

    def serve_step(params, batch, caches, cache_len):
        logits, caches = tf.decode_step(params, batch, caches, cache_len, cfg,
                                        unroll=unroll)
        next_tokens = jnp.argmax(logits, axis=-1)
        return next_tokens, logits, caches

    return serve_step
