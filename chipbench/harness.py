"""One run of one cell: set-up, the measured window, an optional traced job,
the correctness check, and the result line.

The window drives the serving entry ``repro.launch.serve.serve`` as users of
the batch entry do: one call per batch job, back to back (a closed loop),
each job's prompts and weights made from a seed drawn from the run's seed.
A job that starts before the window's seconds have passed runs to its end,
and the window ends with it. Each job is timed on the host clock from the
call until its logits are ready on the device.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from chipbench import check, spec, traffic
from chipbench import trace as trace_lib
from chipbench.peaks import peaks

TRACE_DIR = spec.ROOT / ".chipbench" / "trace"
TRACED_SPAN = "chipbench.traced_job"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# the served model's names for what the configuration file calls them
PROGRAM_KEYS = {
    "num_layers": "num_hidden_layers", "d_model": "hidden_size",
    "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "norm": "norm", "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings",
    "dtype": "dtype", "qkv_bias": "use_bias", "sliding_window": "sliding_window",
    "family": "family", "rope": "position_embedding",
}
PROGRAM_ACT = {"gelu": "gelu_tanh"}


class NoChip(RuntimeError):
    pass


class MissingMetric(RuntimeError):
    """A metric the cell declares read nothing: its reader found no data."""


@dataclasses.dataclass
class JobRecord:
    seed: int
    batch: int
    prompt_len: int
    gen: int
    wall_s: float
    compile_s: float
    prefill_s: float
    decode_s: float
    prompt: np.ndarray
    tokens: np.ndarray
    rows: list[int]            # the rows whose logits are kept for the check
    logits: object = None      # their logits, (len(rows), gen, vocab), on the device

    @property
    def decode_steps(self) -> int:
        return self.gen - 1


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    config: dict
    traffic: dict
    chips: int
    peaks: dict | None
    setup_s: float
    window_s: float
    jobs: list[JobRecord]
    memory_peak_bytes: int
    trace: trace_lib.Trace | None = None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def attached(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"chipbench: platform {d0.platform}, device_kind {d0.device_kind}, "
        f"count {len(devs)}")
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d0.platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; {len(devs)} attached")
    return devs


class CompileCounter:
    """XLA compile requests, and how many of them the persistent cache served."""

    def __init__(self):
        from jax import monitoring
        self.requests = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE:
            self.requests += 1

    def _event(self, event, **_kw):
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.hits


def config_mismatches(model_cfg, cfg: dict) -> list[str]:
    """Keys where the model the program serves differs from the file."""
    out = []
    for prog_key, file_key in PROGRAM_KEYS.items():
        if file_key not in cfg:
            continue
        got = getattr(model_cfg, prog_key)
        if got != cfg[file_key]:
            out.append(f"{file_key}: program {got!r}, file {cfg[file_key]!r}")
    act = PROGRAM_ACT.get(model_cfg.activation, model_cfg.activation)
    if act != cfg["hidden_act"]:
        out.append(f"hidden_act: program {act!r}, file {cfg['hidden_act']!r}")
    return out


def run_job(serve, job: traffic.Job, arch: str, mesh, serve_kwargs: dict,
            keep: list[int] = ()) -> JobRecord:
    """One call of the serving entry, timed; the logits of the rows in
    ``keep`` stay on the device for the check, the rest are dropped."""
    import jax
    t0 = time.perf_counter()
    res = serve(arch, batch=job.batch, prompt_len=job.prompt_len, gen=job.gen,
                seed=job.seed, mesh=mesh, **serve_kwargs)
    jax.block_until_ready(res.logits)
    wall = time.perf_counter() - t0
    rec = JobRecord(job.seed, job.batch, job.prompt_len, job.gen, wall,
                    res.compile_s, res.prefill_s,
                    res.ms_per_token * max(job.gen - 1, 1) * 1e-3,
                    np.asarray(res.prompt), np.asarray(res.tokens), list(keep),
                    res.logits[np.asarray(keep)] if len(keep) else None)
    log(json.dumps({"job": job.seed, "wall_s": wall, "compile_s": res.compile_s,
                    "prefill_s": res.prefill_s, "ms_per_token": res.ms_per_token}))
    return rec


def traced_job(serve, job, arch, mesh, serve_kwargs):
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TRACED_SPAN):
            rec = run_job(serve, job, arch, mesh, serve_kwargs)
    finally:
        jax.profiler.stop_trace()
    path = max(TRACE_DIR.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return rec, trace_lib.reduce(path, TRACED_SPAN)


def correctness(cell: spec.Cell, jobs: list[JobRecord], seed: int,
                mismatches: list[str], control: bool = False) -> tuple[dict, int]:
    """The numbers compared, each with its limit, and the failed requests.
    With ``control`` the float8 reference stands in for the served logits
    (``check.compare``)."""
    cfg, lim = cell.config, cell.limits
    vocab = cfg["vocab_size"]
    prompt_bad = sum(int(np.any(j.prompt != traffic.prompts(traffic.Job(
        j.seed, j.batch, j.prompt_len, j.gen), vocab), axis=1).sum()) for j in jobs)
    bad_rows = sum(int(np.any((j.tokens < 0) | (j.tokens >= vocab), axis=1).sum())
                   if j.tokens.shape == (j.batch, j.gen) else j.batch for j in jobs)
    rms = altered = None
    if bad_rows == 0:
        ref = spec.reference(cfg)
        sq = n = altered = 0
        for j, take in check.sample(jobs, lim["check_requests"], seed):
            job, rows = jobs[j], jobs[j].rows[:take]
            s, c, a = check.compare(ref, cfg, job.seed, job.prompt[rows], job.tokens[rows],
                                    job.logits[:take], control)
            sq, n, altered = sq + s, n + c, altered + a
        rms = math.sqrt(sq / n)
    checks = {
        "logit_rms": {"value": rms, "limit": lim["logit_rms"]},
        "tokens_not_argmax": {"value": altered, "limit": 0},
        "prompt_rows_wrong": {"value": prompt_bad, "limit": 0},
        "token_rows_invalid": {"value": bad_rows, "limit": 0},
        "config_keys_differ": {"value": len(mismatches), "limit": 0},
    }
    return checks, bad_rows


def passes(checks: dict) -> bool:
    return all(c["value"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        serve_kwargs: dict | None = None) -> dict:
    """One run; returns the result object (the caller prints it)."""
    devs = attached(cell.chips, require_tpu)
    import jax
    from repro.launch import serve as serve_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_device_mesh

    serve = serve_mod.serve
    serve_kwargs = serve_kwargs or {}
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    pk = peaks(devs[0].device_kind) if require_tpu else None
    cfg, mix = cell.config, cell.traffic
    arch = cfg["arch"]
    mismatches = config_mismatches(
        serve_mod.serving_arch(arch, reduced=serve_kwargs.get("reduced", False)).model, cfg)
    for m in mismatches:
        log(f"config differs: {m}")
    mesh = make_device_mesh(cfg["mesh"]["model"])
    counter = CompileCounter()

    run_job(serve, traffic.job(mix, seed, traffic.WARMUP), arch, mesh, serve_kwargs)
    setup_s = time.perf_counter() - t_start

    before = counter.snapshot()
    jobs: list[JobRecord] = []
    t0 = time.perf_counter()
    while not jobs or time.perf_counter() - t0 < seconds:
        keep = check.job_rows(seed, len(jobs), mix["batch"], cell.limits["check_requests"])
        jobs.append(run_job(serve, traffic.job(mix, seed, traffic.WINDOW, len(jobs)),
                            arch, mesh, serve_kwargs, keep))
    window_s = time.perf_counter() - t0
    after = counter.snapshot()
    log(json.dumps({"window_jobs": len(jobs), "window_s": window_s,
                    "window_xla_compiles": (after[0] - before[0]) - (after[1] - before[1]),
                    "window_cache_loads": after[1] - before[1]}))

    tr = None
    if trace:
        _, tr = traced_job(serve, traffic.job(mix, seed, traffic.TRACED), arch, mesh,
                           serve_kwargs)
    used = devs[:cell.chips]
    peak_each = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    log(json.dumps({"peak_bytes_in_use": peak_each}))

    run_rec = Run(cfg, mix, cell.chips, pk, setup_s, window_s, jobs, max(peak_each), tr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(run_rec)
        if value is None:
            raise MissingMetric(f"{m['name']} read nothing in {cell.name}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks, failed = correctness(cell, jobs, seed, mismatches)
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
              "memory_peak_bytes": max(peak_each)}
    result = {"correct": passes(checks), "attempted": sum(j.batch for j in jobs),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None and tr.devices:
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(tr),
            "idle_gaps": trace_lib.idle_gaps(tr, tr.devices[0], skip=(TRACED_SPAN,)),
        }
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = spec.resolve(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    except (NoChip, MissingMetric) as e:
        log(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
