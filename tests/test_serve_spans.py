"""The serving entry's phase spans: in the profiler trace, nested in the
call's ``serve`` span, and the source of ``ServeResult``'s timings. A call
whose programs were compiled by an earlier call (a reuse) opens no
``serve.lower.*`` or ``serve.compile.*`` span."""
from collections import Counter

import jax
import pytest

from chipbench import trace as trace_lib
from repro.launch import serve as serve_mod
from repro.launch.serve import serve

GEN = 4
PHASES = {"serve.init": 1, "serve.lower.prefill": 1, "serve.compile.prefill": 1,
          "serve.lower.decode": 1, "serve.compile.decode": 1, "serve.prefill": 1,
          "serve.decode": 1, "serve.decode_step": GEN - 1, "serve.gather": 1}
REUSE_PHASES = {name: n for name, n in PHASES.items()
                if not name.startswith(("serve.lower.", "serve.compile."))}


@pytest.fixture(autouse=True)
def programs(monkeypatch):
    """A table of compiled programs of this test's own: its first call lowers."""
    monkeypatch.setattr(serve_mod, "_PROGRAMS", {})


def _serve():
    return serve("starcoder2-3b", reduced=True, batch=2, prompt_len=8, gen=GEN, seed=3)


def _timings_come_from_the_spans(res):
    secs = {name: s for name, (_, s) in res.spans.items()}
    assert res.prefill_s == secs["serve.prefill"]
    assert res.ms_per_token == secs["serve.decode"] / (GEN - 1) * 1e3
    assert res.compile_s == sum(secs.get(f"serve.{step}.{prog}", 0.0)
                                for step in ("lower", "compile")
                                for prog in ("prefill", "decode"))


def _traced_serve(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = _serve()
    finally:
        jax.profiler.stop_trace()
    return res, trace_lib.reduce(next(tmp_path.rglob("*.xplane.pb")), "serve")


def _spans_nest_in_serve(res, tr, phases_want):
    (job,) = [s for s in tr.host if s.name == "serve"]
    phases = [s for s in tr.host if s.name.startswith("serve.")]
    assert Counter(s.name for s in phases) == phases_want
    assert all(job.start <= s.start and s.end <= job.end for s in phases)
    assert trace_lib.union_ns(phases, tr.window) >= 0.9 * job.dur
    counts = {name: n for name, (n, _) in res.spans.items()}
    assert counts == {"serve": 1, **phases_want}
    for name, (_, secs) in res.spans.items():
        traced = sum(s.dur for s in tr.host if s.name == name) * 1e-9
        assert secs <= traced < secs + 0.05, name
    _timings_come_from_the_spans(res)


def test_phase_spans_nest_in_serve_in_the_trace(tmp_path):
    res, tr = _traced_serve(tmp_path)
    _spans_nest_in_serve(res, tr, PHASES)


def test_a_reuse_opens_no_lower_or_compile_span_in_the_trace(tmp_path):
    assert _serve().compile_s > 0
    res, tr = _traced_serve(tmp_path)
    _spans_nest_in_serve(res, tr, REUSE_PHASES)
    assert res.compile_s == 0
    assert not [s for s in tr.host
                if s.name.startswith(("serve.lower", "serve.compile"))]


def test_spans_are_recorded_with_no_profiler_running():
    res = _serve()
    assert {name: n for name, (n, _) in res.spans.items()} == {"serve": 1, **PHASES}
    assert all(secs > 0 for _, secs in res.spans.values())
    assert res.spans["serve"][1] >= sum(
        secs for name, (_, secs) in res.spans.items()
        if name.startswith("serve.") and name != "serve.decode_step")
    _timings_come_from_the_spans(res)
    reuse = _serve()
    counts = {name: n for name, (n, _) in reuse.spans.items()}
    assert counts == {"serve": 1, **REUSE_PHASES}
    assert reuse.compile_s == 0
    _timings_come_from_the_spans(reuse)
