"""The serving entry's own phase spans in the traced job.

``repro.launch.serve.serve`` opens a ``serve`` span per call and a
``serve.<phase>`` span around each phase inside it (``launch/spans.py``);
the profiler writes them on the host planes, on the device's clock. A job
whose program opens no span of a phase reads 0 for it; a trace without a
``serve`` span (no trace, or a program without the spans) reads nothing.
"""
from __future__ import annotations

from chipbench import trace as trace_lib

JOB = "serve"


def jobs(run) -> list[trace_lib.Span]:
    """The ``serve`` spans inside the traced window."""
    if run.trace is None:
        return []
    lo, hi = run.trace.window
    return [s for s in run.trace.host if s.name == JOB and lo <= s.start and s.end <= hi]


def spans(run, phase: str, job: trace_lib.Span) -> list[trace_lib.Span]:
    """The spans of ``phase`` (``serve.lower`` takes in ``serve.lower.decode``)
    inside ``job``."""
    return [s for s in run.trace.host
            if (s.name == phase or s.name.startswith(phase + "."))
            and job.start <= s.start and s.end <= job.end]


def ms_per_job(run, phase: str) -> float | None:
    js = jobs(run)
    if not js:
        return None
    return 1e-6 * sum(s.dur for j in js for s in spans(run, phase, j)) / len(js)


def per_job(run, phase: str) -> float | None:
    js = jobs(run)
    return sum(len(spans(run, phase, j)) for j in js) / len(js) if js else None
