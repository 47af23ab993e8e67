"""Prefill FLOPs the model needs (counts.py) over the prefill program's
device time, as a share of the chips' bf16 peak."""
from chipbench import counts, programs


def read(run):
    t = programs.mean_call_s(run, programs.PREFILL)
    if t is None or run.peaks is None:
        return None
    mix = run.traffic
    flops = counts.prefill_flops(run.config, mix["batch"], mix["prompt_len"])
    return 100.0 * flops / (t * run.chips * run.peaks["bf16_flops"])
