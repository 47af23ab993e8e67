"""Device time of the serving entry's two step programs, read from the trace.

The prefill program is the jitted ``prefill_into`` and the decode program
the jitted ``serve_step`` of ``repro.launch.serve``; the trace names each
call after its jitted function.
"""
from __future__ import annotations

PREFILL, DECODE = "prefill_into", "serve_step"


def calls(run, program: str) -> list:
    """Calls of ``program`` on the first chip within the traced window."""
    if run.trace is None or not run.trace.devices:
        return []
    return run.trace.calls(run.trace.devices[0], program)


def mean_call_s(run, program: str) -> float | None:
    c = calls(run, program)
    return sum(s.dur for s in c) * 1e-9 / len(c) if c else None
