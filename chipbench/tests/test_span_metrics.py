"""The readers of the serving entry's phase spans, on hand-made traces."""
from __future__ import annotations

import pytest

from chipbench import harness, spec
from chipbench import trace as trace_lib

S = trace_lib.Span
MS = 1e6  # ns

# one job: 100 ms traced window, the ``serve`` call 5-95 ms, its phases
# back to back from 5 to 93 ms, and a JAX host event inside a lowering
JOB = [S("serve", 5 * MS, 95 * MS),
       S("lower_sharding_computation", 21 * MS, 29 * MS)]
PHASES = [S("serve.init", 5 * MS, 20 * MS),
          S("serve.lower.prefill", 20 * MS, 30 * MS),
          S("serve.compile.prefill", 30 * MS, 32 * MS),
          S("serve.lower.decode", 32 * MS, 40 * MS),
          S("serve.compile.decode", 40 * MS, 41 * MS),
          S("serve.prefill", 41 * MS, 60 * MS),
          S("serve.decode", 60 * MS, 85 * MS),
          S("serve.decode_step", 60 * MS, 60.5 * MS),
          S("serve.decode_step", 70 * MS, 70.25 * MS),
          S("serve.gather", 85 * MS, 93 * MS)]
# 47 ms busy on chip 0, all inside phases: 53 ms idle, 12 of them outside
# every phase (0-5 and 93-100 ms)
BUSY = [S("init", 10 * MS, 18 * MS), S("prefill_into", 41 * MS, 59 * MS),
        S("serve_step", 61 * MS, 69 * MS), S("serve_step", 71 * MS, 84 * MS)]

EXPECTED = {"entry_lower_ms": 18.0, "entry_load_ms": 3.0, "entry_init_ms": 15.0,
            "entry_gather_ms": 8.0, "decode_dispatch_ms": 0.375,
            "lowerings_per_job": 2.0, "idle_unnamed_share": 100.0 * 12 / 53}
# with the ``serve`` span and none of the phases: all idle time is unnamed
ABSENT = dict.fromkeys(EXPECTED, 0.0) | {"idle_unnamed_share": 100.0}


def _run(host, jobs=1):
    """A traced run of ``jobs`` copies of the job, 100 ms apart."""
    def shifted(spans):
        return [S(s.name, s.start + k * 100 * MS, s.end + k * 100 * MS)
                for k in range(jobs) for s in spans]
    tr = None if host is None else trace_lib.Trace(
        [trace_lib.DeviceTrace(0, shifted(BUSY), [])], shifted(host),
        (0.0, jobs * 100 * MS))
    return harness.Run({}, {}, 1, None, 0.0, 0.0, [], 0, tr)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_value_for_hand_made_spans(name):
    assert spec.reader(name)(_run(JOB + PHASES)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_job_of_two_reads_alike(name):
    assert spec.reader(name)(_run(JOB + PHASES, jobs=2)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_job_without_the_phase_reads_zero(name):
    assert spec.reader(name)(_run(JOB)) == pytest.approx(ABSENT[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_no_trace_or_no_serve_span_reads_nothing(name):
    assert spec.reader(name)(_run(None)) is None
    assert spec.reader(name)(_run(PHASES[1:2])) is None
