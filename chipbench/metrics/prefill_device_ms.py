"""Device time of one call of the prefill program, from the trace."""
from chipbench import programs


def read(run):
    t = programs.mean_call_s(run, programs.PREFILL)
    return None if t is None else 1e3 * t
