#!/usr/bin/env python3
"""Smoke test of the serving main path on a TPU.

    python chip_smoke.py             # one chip: starcoder2-3b + attention kernels
    python chip_smoke.py --chips 4   # four chips: nemotron-4-15b sharded model=4

One chip: serves starcoder2-3b at published widths (30 x 3072, bf16) through
``repro.launch.serve.serve``, batch 8 x prompt 512 + 32 new tokens, and
checks the last decode step's logits against a teacher-forced prefill over
prompt + generated tokens. Then it compiles the flash and paged attention
kernels at starcoder2-3b head shapes and checks them against ``ref.py``.

Four chips: serves nemotron-4-15b at published widths on a (data=1, model=4)
mesh, prints each device's peak bytes, and compares a 4-layer cut of the
same widths run unsharded on device 0 and sharded over the four chips.

Weights and prompts come from ``--seed``; nothing is read from disk. Every
phase runs in this one process. There is no CPU fallback: without a TPU the
script exits non-zero and prints no result. The last line of a passing run
is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import flash_attention, paged_attention  # noqa: E402
from repro.kernels.backend import use_interpret  # noqa: E402
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa: E402
from repro.kernels.paged_attention.ref import paged_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_device_mesh, mesh_context  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    ServeResult,
    init_placed_params,
    serve,
    serving_arch,
)
from repro.launch.step import build_prefill_step  # noqa: E402

BATCH, PROMPT_LEN, GEN = 8, 512, 32

# Largest |logit difference| allowed between two bf16 computations of the
# same logits (cached decode vs full forward; sharded vs unsharded). The
# teacher-forced comparison measured on the CPU (seed 0): 3e-7 in float32 on
# the reduced config; 0.0078 in bf16 on the reduced config at 2 and 30
# layers (logit std 0.16); 0.0469 in bf16 at starcoder2-3b's published
# widths cut to 2 and 4 layers (logit std 1.1), i.e. 1-2 bf16 ulps of a
# logit near 6. The bound gives that ~5x headroom. A wrong cache slot or
# position moves logits by about their std, 1.1 to 1.6 at these widths.
LOGIT_BOUND = 0.25

# Kernel vs ref.py in bf16 (the kernels accumulate in f32, the ref rounds
# scores to bf16). At these shapes in interpret mode on the CPU: 0.0156
# (flash), 0.0039 (paged); the bound gives ~3x headroom.
KERNEL_BOUND = 5e-2


def _f32(x) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.float32))


def _peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def teacher_forced_gap(arch_name: str, result: ServeResult, seed: int) -> float:
    """max |last decode logits - prefill logits over prompt + generated|."""
    arch = serving_arch(arch_name)
    mesh = make_device_mesh()
    params = init_placed_params(arch, mesh, seed)
    seq = np.concatenate([result.prompt, result.tokens[:, :-1]], axis=1)
    with mesh_context(mesh):
        _, ref_logits, _ = jax.jit(build_prefill_step(arch))(
            params, {"tokens": jnp.asarray(seq)})
    return float(np.abs(_f32(result.logits[:, -1]) - _f32(ref_logits)).max())


def greedy_agreement(ref: ServeResult, test: ServeResult) -> tuple[float, int]:
    """Compare two greedy runs of the same model and prompts.

    Row by row, the runs see the same inputs up to and including the first
    step whose tokens differ; their logits must agree within LOGIT_BOUND
    over that span. A differing token is allowed only at a near-tie: where
    ``ref``'s margin between the two tokens is within 2 * LOGIT_BOUND, the
    most two bounded logits can move apart. Returns (max |logit diff|,
    rows that diverged at a near-tie)."""
    lr, lt = _f32(ref.logits), _f32(test.logits)
    worst, diverged = 0.0, 0
    for b in range(ref.tokens.shape[0]):
        differ = np.flatnonzero(ref.tokens[b] != test.tokens[b])
        end = differ[0] + 1 if differ.size else ref.tokens.shape[1]
        worst = max(worst, float(np.abs(lr[b, :end] - lt[b, :end]).max()))
        if differ.size:
            t = differ[0]
            margin = lr[b, t, ref.tokens[b, t]] - lr[b, t, test.tokens[b, t]]
            if margin > 2 * LOGIT_BOUND:
                raise AssertionError(
                    f"row {b} step {t}: tokens {ref.tokens[b, t]} vs "
                    f"{test.tokens[b, t]} differ at a logit margin of {margin}")
            diverged += 1
    if worst > LOGIT_BOUND:
        raise AssertionError(f"logits differ by {worst} > {LOGIT_BOUND}")
    return worst, diverged


def _check_compiled(fn, *args) -> np.ndarray:
    """Run the jitted kernel wrapper compiled to a TPU custom call."""
    compiled = fn.lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{fn.__name__}: no tpu_custom_call in the HLO")
    return _f32(compiled(*args))


def check_kernels(seed: int) -> None:
    """Flash and paged attention at starcoder2-3b head shapes vs ref.py."""
    if use_interpret():
        raise AssertionError("kernels would run in interpret mode")
    cfg = serving_arch("starcoder2-3b").model
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.key(seed), 8)
    bf16 = jnp.bfloat16

    s = 2048
    q = jax.random.normal(ks[0], (1, s, hq, dh), bf16)
    k = jax.random.normal(ks[1], (1, s, hkv, dh), bf16)
    v = jax.random.normal(ks[2], (1, s, hkv, dh), bf16)
    out = _check_compiled(flash_attention, q, k, v)
    diff = float(np.abs(out - _f32(jax.jit(flash_attention_ref)(q, k, v))).max())
    print(f"flash_attention (1, {s}, Hq {hq}, Hkv {hkv}, Dh {dh}) bf16: "
          f"compiled, max |kernel - ref| = {diff} (bound {KERNEL_BOUND})")
    if not diff <= KERNEL_BOUND:
        raise AssertionError(f"flash_attention differs from ref by {diff}")

    b, page, pages_per_seq = BATCH, 16, 128
    npages = b * pages_per_seq
    pool_k = jax.random.normal(ks[3], (npages, page, hkv, dh), bf16)
    pool_v = jax.random.normal(ks[4], (npages, page, hkv, dh), bf16)
    qd = jax.random.normal(ks[5], (b, hq, dh), bf16)
    table = jax.random.permutation(ks[6], npages).reshape(b, pages_per_seq)
    table = table.astype(jnp.int32)
    lens = jax.random.randint(ks[7], (b,), 1, page * pages_per_seq + 1)
    lens = lens.at[0].set(page * pages_per_seq).astype(jnp.int32)
    args = (qd, pool_k, pool_v, table, lens)
    out = _check_compiled(paged_attention, *args)
    diff = float(np.abs(out - _f32(jax.jit(paged_attention_ref)(*args))).max())
    print(f"paged_attention (B {b}, {npages} pages x {page} x Hkv {hkv} x "
          f"Dh {dh}) bf16: compiled, max |kernel - ref| = {diff} "
          f"(bound {KERNEL_BOUND})")
    if not diff <= KERNEL_BOUND:
        raise AssertionError(f"paged_attention differs from ref by {diff}")


def _describe(arch_name: str, layers: int | None = None) -> str:
    cfg = serving_arch(arch_name, layers=layers).model
    return (f"{arch_name}: {cfg.num_layers} layers x d_model {cfg.d_model}, "
            f"Hq {cfg.num_heads} / Hkv {cfg.num_kv_heads} x Dh {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
            f"batch {BATCH}, prompt {PROMPT_LEN}, {GEN} new tokens")


def _report(result: ServeResult, vocab: int) -> None:
    b, gen = result.tokens.shape
    if result.logits.shape != (b, gen, vocab):
        raise AssertionError(f"logits of shape {result.logits.shape}")
    if not np.isfinite(_f32(result.logits)).all():
        raise AssertionError("non-finite logits")
    print(f"  compile {result.compile_s:.3f} s, prefill {result.prefill_s:.4f} s, "
          f"decode {result.ms_per_token:.3f} ms/token (bring-up observations)")


def one_chip(seed: int) -> None:
    print(_describe("starcoder2-3b"))
    result = serve("starcoder2-3b", reduced=False, batch=BATCH,
                   prompt_len=PROMPT_LEN, gen=GEN, seed=seed)
    _report(result, serving_arch("starcoder2-3b").model.padded_vocab)
    print(f"  peak_bytes_in_use after serving: {_peak_bytes(jax.devices()[0])}")
    gap = teacher_forced_gap("starcoder2-3b", result, seed)
    print(f"teacher-forced check: max |decode logits - prefill logits| = {gap} "
          f"(bound {LOGIT_BOUND})")
    if not gap <= LOGIT_BOUND:
        raise AssertionError(f"decode vs prefill logits differ by {gap}")
    check_kernels(seed)
    print(f"peak_bytes_in_use at end: {_peak_bytes(jax.devices()[0])}")


def four_chips(seed: int) -> None:
    name = "nemotron-4-15b"
    mesh = make_device_mesh(model=4)
    print(_describe(name) + "; mesh (data=1, model=4)")
    result = serve(name, reduced=False, batch=BATCH, prompt_len=PROMPT_LEN,
                   gen=GEN, seed=seed, mesh=mesh)
    _report(result, serving_arch(name).model.padded_vocab)
    for d in mesh.devices.flat:
        print(f"  device {d.id}: peak_bytes_in_use {_peak_bytes(d)}")
    del result

    print("comparison: " + _describe(name, layers=4)
          + "; unsharded on device 0 vs sharded model=4")
    ref = serve(name, layers=4, batch=BATCH, prompt_len=PROMPT_LEN, gen=GEN,
                seed=seed)
    test = serve(name, layers=4, batch=BATCH, prompt_len=PROMPT_LEN, gen=GEN,
                 seed=seed, mesh=mesh)
    worst, diverged = greedy_agreement(ref, test)
    print(f"  max |logit diff| over shared inputs = {worst} (bound {LOGIT_BOUND}); "
          f"tokens equal in {BATCH - diverged}/{BATCH} rows, the rest split "
          f"at a near-tie")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {device}")
    if device["platform"] != "tpu":
        print("no TPU found; this smoke test has no CPU fallback",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
