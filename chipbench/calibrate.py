"""Readings that the correctness limit of a cell is set from.

    python chipbench/calibrate.py --workload sc2_3b.codegen --seeds 1,2,3 \
        [--control-seeds 1,2,3]

In one process: the warm-up job, then for each seed the window jobs that a
run compares from (at least two, at the cell's own sizes, seeded as a run
seeds them) and the comparison of ``harness.correctness`` on the requests a
run would draw. For each seed of ``--control-seeds`` the same comparison is
made again with the float8 control in the program's place, through the same
limit. One JSON line per reading on standard output, then a summary: the
lower reading (largest sound ``logit_rms``) and the upper reading (smallest
control ``logit_rms``). Benchmark runs never run the control.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import check, harness, spec, traffic  # noqa: E402

NUMBER = "logit_rms"  # the number compared with the reference


def readings(cell, seeds, control_seeds, *, require_tpu=True, serve_kwargs=None):
    harness.attached(cell.chips, require_tpu)
    from repro.launch import serve as serve_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_device_mesh

    serve_kwargs = serve_kwargs or {}
    enable_compile_cache()
    arch, mix = cell.config["arch"], cell.traffic
    mesh = make_device_mesh(cell.config["mesh"]["model"])
    mismatches = harness.config_mismatches(
        serve_mod.serving_arch(arch, reduced=serve_kwargs.get("reduced", False)).model,
        cell.config)
    seeds = list(dict.fromkeys(seeds + control_seeds))
    harness.run_job(serve_mod.serve, traffic.job(mix, seeds[0], traffic.WARMUP), arch,
                    mesh, serve_kwargs)
    n_req = cell.limits["check_requests"]
    n_jobs = max(2, -(-n_req // mix["batch"]))
    out = []
    for seed in seeds:
        jobs = [harness.run_job(serve_mod.serve, traffic.job(mix, seed, traffic.WINDOW, i),
                                arch, mesh, serve_kwargs,
                                check.job_rows(seed, i, mix["batch"], n_req))
                for i in range(n_jobs)]
        for control in (False, True) if seed in control_seeds else (False,):
            t0 = time.perf_counter()
            checks, _ = harness.correctness(cell, jobs, seed, mismatches, control)
            rec = {"seed": seed, "control": control,
                   **{k: c["value"] for k, c in checks.items()},
                   "limit": checks[NUMBER]["limit"], "correct": harness.passes(checks),
                   "reference_s": time.perf_counter() - t0}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


def summary(cell_name: str, recs: list[dict]) -> dict:
    sound = [r[NUMBER] for r in recs if not r["control"]]
    low = [r[NUMBER] for r in recs if r["control"]]
    out = {"workload": cell_name, "seeds": len(sound), "lower": max(sound)}
    if low:
        out.update(control_seeds=len(low), upper=min(low),
                   control_correct=[r["correct"] for r in recs if r["control"]])
    return out


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    args = ap.parse_args()
    cell = spec.resolve(args.workload)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    try:
        recs = readings(cell, ints(args.seeds), ints(args.control_seeds))
    except harness.NoChip as e:
        harness.log(f"chipbench: {e}")
        return 2
    print(json.dumps(summary(cell.name, recs)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
