"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --steps 50 [--no-reduced] [--batch 8 --seq 128] [--ckpt-dir DIR]

--reduced (the default) trains the arch's reduced config on CPU (the
examples/ and tests use this); --no-reduced selects published widths, which
need the optimizer offload this driver does not wire yet.
Integrates: residency planning, UM prefetch input pipeline, AdamW(+int8),
checkpoint/restart via TrainRunner, straggler watchdog.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, synthetic_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.step import build_train_step
from repro.models import init_params
from repro.optim import init_state
from repro.launch.step import _adamw_cfg
from repro.runtime import TrainRunner


def train(arch_name: str, *, steps: int = 50, reduced: bool = True,
          batch: int = 8, seq: int = 128, ckpt_dir: str | None = None,
          checkpoint_every: int = 20, fault_schedule=(), log_every: int = 10,
          seed: int = 0):
    arch = get_config(arch_name)
    if reduced:
        arch = dataclasses.replace(
            arch, model=arch.model.reduce(),
            train=dataclasses.replace(arch.train, microbatches=1,
                                      learning_rate=3e-3,
                                      warmup_steps=max(2, steps // 10)),
        )
    shape = ShapeConfig("cli", seq_len=seq, global_batch=batch, kind="train")
    mesh = None  # single-device path; the dry-run covers the mesh path

    params = init_params(jax.random.key(seed), arch.model)
    opt = init_state(params, _adamw_cfg(arch, None))
    step_fn_inner = build_train_step(arch, shape, mesh, None,
                                     total_steps=steps)
    jitted = jax.jit(step_fn_inner, donate_argnums=(0, 1))

    def step_fn(state, batch_np, step):
        params, opt = state
        batch_dev = jax.tree.map(jnp.asarray, batch_np)
        params, opt, metrics = jitted(params, opt, batch_dev, jnp.int32(step))
        return (params, opt), metrics

    ckpt = Checkpointer(ckpt_dir or f"/tmp/repro_ckpt_{arch_name}",
                        keep_last=2)
    runner = TrainRunner(step_fn, ckpt, checkpoint_every=checkpoint_every,
                         fault_schedule=fault_schedule)
    batches = []
    gen = synthetic_batches(arch.model, shape, DataConfig(seed=seed))
    for _ in range(min(steps, 16)):
        batches.append(next(gen))

    t0 = time.time()
    state, report = runner.run((params, opt), batches, steps)
    dt = time.time() - t0
    if report.losses:
        print(f"[{arch_name}] steps={report.steps_completed} "
              f"restarts={report.restarts} "
              f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f} "
              f"({dt:.1f}s, {dt / max(report.steps_completed, 1) * 1e3:.0f} ms/step)")
    return state, report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="starcoder2-3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, steps=args.steps, reduced=args.reduced,
          batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir)


if __name__ == "__main__":
    main()
