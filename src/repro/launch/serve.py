"""Batched serving driver: prefill + decode loop with greedy sampling.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b \
        --batch 8 --prompt-len 512 --gen 32 [--reduced]

Published widths by default; ``--reduced`` serves the arch's tiny
same-family config (CPU tests and examples).

Serve path exercises: prefill -> stacked KV caches -> decode_step loop
(ring-buffer caches for SWA archs; recurrent state for rwkv/hymba).  Params
and caches are created already placed by the mesh's shardings, so a model
larger than one chip is never assembled on device 0.  The paged host KV
tier is exercised by examples/oversubscribe_demo.py.

The prefill and decode programs are compiled once per key (the model config,
batch, prompt length, cache length, mesh, and the globals of the package's
modules: tuning constants, and functions that code in the same process may
rebind) and reused by every later call in the process with that key; weights
and caches are still made anew by each call. The key is looked up inside the
call's ``serve.init`` span; only a miss opens the lowering and compiling spans.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, ArchConfig, ModelConfig, ShapeConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, mesh_context
from repro.launch.sharding import param_shardings
from repro.launch.spans import SpanRecorder
from repro.launch.step import (
    abstract_params,
    build_prefill_step,
    build_serve_step,
    make_shardings,
)
from repro.models import init_caches, init_params


@dataclasses.dataclass(frozen=True)
class ServeResult:
    prompt: np.ndarray     # (B, prompt_len[, K]) the seeded prompt
    tokens: np.ndarray     # (B, gen[, K]) greedy tokens; [:, 0] from prefill
    logits: jax.Array      # (B, gen[, K], V) the logits each token came from
    compile_s: float       # lowering + compiling both programs; 0 on a reuse
    prefill_s: float
    ms_per_token: float    # decode steps, to block_until_ready
    spans: dict[str, tuple[int, float]]  # (count, seconds) of each serve* span


def serving_arch(arch_name: str, *, reduced: bool = False,
                 layers: int | None = None) -> ArchConfig:
    """The arch at published widths, or its reduced config; ``layers``
    cuts depth and nothing else."""
    arch = get_config(arch_name)
    model = arch.model.reduce() if reduced else arch.model
    if layers is not None:
        model = dataclasses.replace(model, num_layers=layers)
    return dataclasses.replace(arch, model=model)


def init_placed_params(arch: ArchConfig, mesh, seed: int):
    """Params made from ``seed`` directly into their mesh shardings."""
    params_sh = param_shardings(arch.model, abstract_params(arch), mesh)
    return jax.jit(init_params, static_argnums=1,
                   out_shardings=params_sh)(jax.random.key(seed), arch.model)


def _fill_caches(caches, prompt_caches, cfg: ModelConfig):
    """Copy the prompt's caches into the max_seq decode caches."""
    if cfg.family == "ssm":
        return prompt_caches  # recurrent state is position-independent
    caches = dict(caches)
    s_cache = min(caches["k"].shape[2], prompt_caches["k"].shape[2])
    for key in ("k", "v"):
        caches[key] = jax.lax.dynamic_update_slice_in_dim(
            caches[key], prompt_caches[key][:, :, -s_cache:], 0, axis=2)
    for key in ("conv", "ssm"):
        if key in caches:
            caches[key] = prompt_caches[key]
    return caches


# Compiled (prefill, decode) pairs, executables only and never arrays, keyed
# by `_program_key`: the model config, (batch, prompt_len, max_seq), the mesh
# and the globals of the package's modules, so that a tuning constant or a
# function rebound in-process gives another key. Past _PROGRAMS_KEPT keys the
# oldest is dropped.
_PROGRAMS: dict[tuple, tuple] = {}
_PROGRAMS_KEPT = 8


class _Same:
    """Equal to another ``_Same`` of the very same object, and holding that
    object, so that its id is not handed to a later one."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __eq__(self, other):
        return isinstance(other, _Same) and self.obj is other.obj

    def __hash__(self):
        return id(self.obj)


_PLAIN = (bool, int, float, str, type(None))


def _module_globals() -> tuple:
    """Every global of every loaded ``repro`` module: plain constants by
    value, anything else by identity. Tracing reads these (tuning constants
    such as ``moe.MOE_GROUP_SIZE``, the layer functions a step calls), so
    rebinding any of them gives another key."""
    return tuple(
        (name, attr, value if type(value) in _PLAIN else _Same(value))
        for name, mod in sorted(sys.modules.items())
        if (name == "repro" or name.startswith("repro.")) and mod is not None
        for attr, value in list(vars(mod).items()))


def _program_key(arch: ArchConfig, mesh, shape: tuple[int, int, int]) -> tuple:
    """The program table's key for ``arch.model`` at ``shape`` (batch,
    prompt_len, max_seq) on ``mesh``, read from the globals as they stand."""
    return (arch.model, shape, mesh) + _module_globals()


def _step_programs(rec: SpanRecorder, arch: ArchConfig, mesh,
                   shape: tuple[int, int, int], shardings, params, pre_batch, caches):
    """Lower and compile ``prefill_into`` and ``serve_step`` for
    ``arch.model`` at ``shape`` on ``mesh``, with their shardings, donating
    the caches, and keep them in the table. The closures capture the config
    only."""
    cfg = arch.model
    params_sh, prompt_sh, tok_sh, caches_sh, scalar_sh = shardings
    prefill_step = build_prefill_step(arch)

    def prefill_into(params, batch_, caches):
        next_tokens, logits, prompt_caches = prefill_step(params, batch_)
        return next_tokens, logits, _fill_caches(caches, prompt_caches, cfg)

    with rec.span("serve.lower.prefill"):
        prefill_l = jax.jit(
            prefill_into, in_shardings=(params_sh, prompt_sh, caches_sh),
            out_shardings=(tok_sh, None, caches_sh), donate_argnums=(2,),
        ).lower(params, pre_batch, caches)
    with rec.span("serve.compile.prefill"):
        prefill_c = prefill_l.compile()
    with rec.span("serve.lower.decode"):
        prompt = pre_batch["tokens"]
        step_tokens = jax.ShapeDtypeStruct(
            prompt.shape[:1] + prompt.shape[2:], jnp.int32)
        decode_l = jax.jit(
            build_serve_step(arch),
            in_shardings=(params_sh, tok_sh, caches_sh, scalar_sh),
            out_shardings=(tok_sh, None, caches_sh), donate_argnums=(2,),
        ).lower(params, {"tokens": step_tokens}, caches,
                jax.ShapeDtypeStruct((), jnp.int32))
    with rec.span("serve.compile.decode"):
        decode_c = decode_l.compile()
    # read again: tracing may have imported a module of the package
    _PROGRAMS[_program_key(arch, mesh, shape)] = prefill_c, decode_c
    if len(_PROGRAMS) > _PROGRAMS_KEPT:
        del _PROGRAMS[next(iter(_PROGRAMS))]
    return prefill_c, decode_c


def serve(arch_name: str, *, reduced: bool = False, layers: int | None = None,
          batch: int = 4, prompt_len: int = 32, gen: int = 16, seed: int = 0,
          mesh=None) -> ServeResult:
    """Greedy-serve ``gen`` tokens for a seeded batch of prompts.

    ``mesh`` defaults to one device, unsharded; on a (data, model) mesh the
    params and caches are sharded by ``launch/step.make_shardings``."""
    rec = SpanRecorder()
    with rec.span("serve", seed=seed, batch=batch, prompt_len=prompt_len, gen=gen):
        arch = serving_arch(arch_name, reduced=reduced, layers=layers)
        cfg = arch.model
        mesh = mesh if mesh is not None else make_device_mesh()
        with rec.span("serve.init"):
            # the caches' sequence dim is sharded over "model"; slots past
            # cache_len are masked, so rounding up changes no result
            model = mesh.shape["model"]
            max_seq = -(-(prompt_len + gen) // model) * model
            shape = ShapeConfig("serve", seq_len=max_seq, global_batch=batch,
                                kind="decode")
            params_sh, _, batch_sh, caches_sh = make_shardings(arch, shape, mesh)
            tok_sh = batch_sh["tokens"]                  # (B[, K]) decode tokens
            prompt_sh = NamedSharding(mesh, P(tok_sh.spec[0], None, *tok_sh.spec[1:]))
            scalar_sh = NamedSharding(mesh, P())
            params = init_placed_params(arch, mesh, seed)
            codebooks = (cfg.num_codebooks,) if cfg.family == "audio" else ()
            prompt = np.random.default_rng(seed).integers(
                0, cfg.vocab_size, (batch, prompt_len) + codebooks).astype(np.int32)
            with mesh_context(mesh):
                caches = jax.jit(init_caches, static_argnums=(0, 1, 2),
                                 out_shardings=caches_sh)(cfg, batch, max_seq)
                pre_batch = {"tokens": jax.device_put(prompt, prompt_sh)}
            programs = _PROGRAMS.get(
                _program_key(arch, mesh, (batch, prompt_len, max_seq)))
            # the init's device work ends in its own span, not in the prefill's
            jax.block_until_ready((params, caches, pre_batch))
        with mesh_context(mesh):
            if programs is None:
                programs = _step_programs(
                    rec, arch, mesh, (batch, prompt_len, max_seq),
                    (params_sh, prompt_sh, tok_sh, caches_sh, scalar_sh),
                    params, pre_batch, caches)
            prefill_c, decode_c = programs

            with rec.span("serve.prefill"):
                next_tokens, logits, caches = prefill_c(params, pre_batch, caches)
                next_tokens.block_until_ready()

            tokens, step_logits = [next_tokens], [logits]
            with rec.span("serve.decode"):
                for i in range(gen - 1):
                    with rec.span("serve.decode_step"):
                        cache_len = jax.device_put(np.int32(prompt_len + i), scalar_sh)
                        next_tokens, logits, caches = decode_c(
                            params, {"tokens": next_tokens}, caches, cache_len)
                    tokens.append(next_tokens)
                    step_logits.append(logits)
                jax.block_until_ready(tokens[-1])

        with rec.span("serve.gather"):
            tokens = np.stack([np.asarray(t) for t in tokens], axis=1)
            logits = jnp.stack(step_logits, axis=1)

    s = rec.seconds
    return ServeResult(
        prompt=prompt,
        tokens=tokens,
        logits=logits,
        compile_s=sum(secs for name, secs in s.items()
                      if name.startswith(("serve.lower.", "serve.compile."))),
        prefill_s=s["serve.prefill"],
        ms_per_token=s["serve.decode"] / max(gen - 1, 1) * 1e3,
        spans=rec.totals(),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's tiny same-family config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    result = serve(args.arch, reduced=args.reduced, batch=args.batch,
                   prompt_len=args.prompt_len, gen=args.gen)
    print(f"[{args.arch}] generated {result.tokens.shape} tokens: "
          f"compile {result.compile_s:.2f}s, prefill {result.prefill_s:.3f}s, "
          f"{result.ms_per_token:.2f} ms/token")
    for name, (count, secs) in result.spans.items():
        print(f"  {name:<22} {count:>5} x  {secs * 1e3:10.2f} ms")


if __name__ == "__main__":
    main()
