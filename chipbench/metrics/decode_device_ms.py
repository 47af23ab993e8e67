"""Device time of one call of the decode program, from the trace."""
from chipbench import programs


def read(run):
    t = programs.mean_call_s(run, programs.DECODE)
    return None if t is None else 1e3 * t
