"""Least time of one decode step on its binding roofline (FLOPs over peak
FLOP/s or bytes over peak bytes/s, counts.py) over the decode program's
device time, in percent."""
from chipbench import counts, programs


def read(run):
    t = programs.mean_call_s(run, programs.DECODE)
    if t is None or run.peaks is None:
        return None
    mix = run.traffic
    live = counts.decode_live(mix["prompt_len"], mix["gen"])
    bound, _ = counts.decode_bound(run.config, mix["batch"], live, run.chips, run.peaks)
    return 100.0 * bound / t
