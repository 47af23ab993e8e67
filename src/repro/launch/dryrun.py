import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), so this module has no __future__ imports.
"""Multi-pod dry-run (EXPERIMENTS.md §Dry-run).

For every (architecture x input shape x mesh) cell:
  1. residency plan (oversubscription decisions recorded),
  2. jax.jit(step).lower(**input_specs).compile() on the production mesh,
  3. memory_analysis()  -> proves per-device fit,
  4. cost_analysis() + HLO collective parse,
  5. L=1/L=2 unrolled cost probes -> scan-corrected roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b \
      --shape train_4k [--multi-pod] [--no-probes] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config, get_shape
from repro.configs.base import ArchConfig, MeshConfig, ShapeConfig
from repro.core.residency import plan_cell
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh, mesh_context
from repro.launch.step import (
    abstract_caches,
    abstract_opt_state,
    abstract_params,
    build_prefill_step,
    build_serve_step,
    build_train_step,
    input_specs,
    make_shardings,
)

GB = 1024**3
DEFAULT_OUT = pathlib.Path("artifacts/dryrun")


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    return {
        "argument_gb": mem.argument_size_in_bytes / GB,
        "output_gb": mem.output_size_in_bytes / GB,
        "temp_gb": mem.temp_size_in_bytes / GB,
        "alias_gb": mem.alias_size_in_bytes / GB,
        "peak_extra_gb": (mem.temp_size_in_bytes + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes) / GB,
    }


def lower_cell(arch: ArchConfig, shape: ShapeConfig, mesh, plan, *,
               unroll: bool = False):
    """Lower + compile one cell's step on `mesh`. Returns (lowered, compiled).

    mesh_context activates the model's shard_hint constraints (SP residual
    stream, seq-replicated KV); probes also unroll the flash KV-block scan
    (``unroll`` reaches it through ``build_prefill_step``).
    """
    with mesh_context(mesh):
        return _lower_cell_inner(arch, shape, mesh, plan, unroll)


def _lower_cell_inner(arch: ArchConfig, shape: ShapeConfig, mesh, plan,
                      unroll: bool):
    params = abstract_params(arch)
    psh, osh, bsh, csh = make_shardings(arch, shape, mesh, plan)
    scalar = NamedSharding(mesh, P())
    if shape.kind == "train":
        step = build_train_step(arch, shape, mesh, plan, unroll=unroll)
        lowered = jax.jit(
            step,
            in_shardings=(psh, osh, bsh, scalar),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
        ).lower(params, abstract_opt_state(arch, plan), input_specs(arch, shape),
                jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        step = build_prefill_step(arch, unroll=unroll)
        # output caches: sequence-sharded like decode caches
        _, _, _, csh_out = make_shardings(
            arch, dataclasses.replace(shape, kind="decode"), mesh, plan)
        lowered = jax.jit(
            step,
            in_shardings=(psh, bsh),
            out_shardings=(None, None, csh_out),
        ).lower(params, input_specs(arch, shape))
    else:  # decode
        step = build_serve_step(arch, unroll=unroll)
        caches = abstract_caches(arch, shape)
        lowered = jax.jit(
            step,
            in_shardings=(psh, bsh, csh, scalar),
            out_shardings=(None, None, csh),
            donate_argnums=(2,),
        ).lower(params, input_specs(arch, shape), caches,
                jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, lowered.compile()


def _probe_stats(arch: ArchConfig, shape: ShapeConfig, mesh, plan, L: int):
    arch_l = dataclasses.replace(arch, model=dataclasses.replace(
        arch.model, num_layers=L))
    plan_l = plan  # plan numbers don't affect lowering except remat/int8 flags
    _, compiled = lower_cell(arch_l, shape, mesh, plan_l, unroll=True)
    cost = compiled.cost_analysis() or {}
    colls = analysis.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(colls.link_bytes),
        "collectives": colls.as_dict(),
    }


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             probes: bool = True, outdir: pathlib.Path = DEFAULT_OUT) -> dict:
    arch = get_config(arch_name)
    shape = get_shape(shape_name)
    mesh_cfg = MeshConfig(multi_pod)
    mesh_tag = "x".join(map(str, mesh_cfg.shape))
    record: dict = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_tag,
        "multi_pod": multi_pod, "chips": mesh_cfg.num_devices,
    }
    ok, reason = arch.supports_shape(shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        _write(record, outdir)
        return record

    plan = plan_cell(arch, shape, mesh_cfg)
    record["residency_plan"] = plan.summary()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        t0 = time.time()
        lowered, compiled = lower_cell(arch, shape, mesh, plan)
        record["compile_s"] = round(time.time() - t0, 1)
        mem = _mem_dict(compiled.memory_analysis())
        record["memory_analysis"] = mem
        cost = compiled.cost_analysis() or {}
        record["cost_analysis_raw"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        record["collectives_raw"] = analysis.parse_collectives(
            compiled.as_text()).as_dict()
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug to surface
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        _write(record, outdir)
        return record

    if probes:
        try:
            p1 = _probe_stats(arch, shape, mesh, plan, 1)
            p2 = _probe_stats(arch, shape, mesh, plan, 2)
            L = arch.model.num_layers
            flops = analysis.extrapolate(p1["flops"], p2["flops"], L)
            flops += analysis.wkv_correction_flops(arch, shape) / mesh_cfg.num_devices
            nbytes = analysis.extrapolate(p1["bytes"], p2["bytes"], L)
            cbytes = analysis.extrapolate(
                p1["collective_bytes"], p2["collective_bytes"], L)
            roof = analysis.Roofline(
                arch=arch_name, shape=shape_name, mesh=mesh_tag,
                chips=mesh_cfg.num_devices,
                hlo_flops_per_chip=flops,
                hlo_bytes_per_chip=nbytes,
                collective_bytes_per_chip=max(cbytes, 0.0),
                model_flops_total=analysis.model_flops(arch, shape),
            )
            record["probes"] = {"L1": p1, "L2": p2}
            record["roofline"] = roof.as_dict()
        except Exception as e:  # noqa: BLE001
            record["probe_error"] = f"{type(e).__name__}: {e}"
            record["probe_traceback"] = traceback.format_exc()[-2000:]

    _write(record, outdir)
    return record


def _write(record: dict, outdir: pathlib.Path) -> None:
    outdir.mkdir(parents=True, exist_ok=True)
    name = f"{record['arch']}_{record['shape']}_{record['mesh']}.json"
    (outdir / name).write_text(json.dumps(record, indent=1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=["train_4k", "prefill_32k",
                                        "decode_32k", "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    args = ap.parse_args()
    out = pathlib.Path(args.out)

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
                for mp in (False, True):
                    cells.append((a, s, mp))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape, args.multi_pod)]

    failures = 0
    for a, s, mp in cells:
        t0 = time.time()
        rec = run_cell(a, s, multi_pod=mp, probes=not args.no_probes, outdir=out)
        status = rec["status"]
        extra = ""
        if status == "ok":
            peak = rec["memory_analysis"].get("peak_extra_gb", 0) + \
                rec["memory_analysis"].get("argument_gb", 0)
            extra = f"perdev={peak:.2f}GB"
            if "roofline" in rec:
                extra += f" bound={rec['roofline']['bound']}"
        elif status == "failed":
            failures += 1
            extra = rec["error"][:120]
        print(f"[{status:7s}] {a:18s} {s:12s} mesh={rec['mesh']:8s} "
              f"({time.time()-t0:5.1f}s) {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
