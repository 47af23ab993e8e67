"""Share of the first chip's idle time in the traced window that no phase
span of the serving entry (``serve.*``) covers."""
from chipbench import phases
from chipbench import trace as trace_lib


def read(run):
    js = phases.jobs(run)
    if not js or not run.trace.devices:
        return None
    tr, dev = run.trace, run.trace.devices[0]
    busy = dev.ops or dev.modules
    named = [s for s in tr.host if s.name.startswith(phases.JOB + ".")]
    idle = tr.window[1] - tr.window[0] - trace_lib.union_ns(busy, tr.window)
    if idle <= 0:
        return 0.0
    unnamed = tr.window[1] - tr.window[0] - trace_lib.union_ns(busy + named, tr.window)
    return 100.0 * unnamed / idle
