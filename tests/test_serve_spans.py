"""The serving entry's phase spans: in the profiler trace, nested in the
call's ``serve`` span, and the source of ``ServeResult``'s timings."""
from collections import Counter

import jax

from chipbench import trace as trace_lib
from repro.launch.serve import serve

GEN = 4
PHASES = {"serve.init": 1, "serve.lower.prefill": 1, "serve.compile.prefill": 1,
          "serve.lower.decode": 1, "serve.compile.decode": 1, "serve.prefill": 1,
          "serve.decode": 1, "serve.decode_step": GEN - 1, "serve.gather": 1}


def _serve():
    return serve("starcoder2-3b", reduced=True, batch=2, prompt_len=8, gen=GEN, seed=3)


def _timings_come_from_the_spans(res):
    secs = {name: s for name, (_, s) in res.spans.items()}
    assert res.prefill_s == secs["serve.prefill"]
    assert res.ms_per_token == secs["serve.decode"] / (GEN - 1) * 1e3
    assert res.compile_s == sum(secs[f"serve.{step}.{prog}"] for step in ("lower", "compile")
                                for prog in ("prefill", "decode"))


def test_phase_spans_nest_in_serve_in_the_trace(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = _serve()
    finally:
        jax.profiler.stop_trace()
    tr = trace_lib.reduce(next(tmp_path.rglob("*.xplane.pb")), "serve")
    (job,) = [s for s in tr.host if s.name == "serve"]
    phases = [s for s in tr.host if s.name.startswith("serve.")]
    assert Counter(s.name for s in phases) == PHASES
    assert all(job.start <= s.start and s.end <= job.end for s in phases)
    assert trace_lib.union_ns(phases, tr.window) >= 0.9 * job.dur
    assert {name: n for name, (n, _) in res.spans.items()} == {"serve": 1, **PHASES}
    for name, (_, secs) in res.spans.items():
        traced = sum(s.dur for s in tr.host if s.name == name) * 1e-9
        assert secs <= traced < secs + 0.05, name
    _timings_come_from_the_spans(res)


def test_spans_are_recorded_with_no_profiler_running():
    res = _serve()
    assert {name: n for name, (n, _) in res.spans.items()} == {"serve": 1, **PHASES}
    assert all(secs > 0 for _, secs in res.spans.values())
    assert res.spans["serve"][1] >= sum(
        secs for name, (_, secs) in res.spans.items()
        if name.startswith("serve.") and name != "serve.decode_step")
    _timings_come_from_the_spans(res)
