"""FLOPs one decode step needs (counts.py, mean live length) over the decode
program's device time, as a share of the chips' bf16 peak."""
from chipbench import counts, programs


def read(run):
    t = programs.mean_call_s(run, programs.DECODE)
    if t is None or run.peaks is None:
        return None
    mix = run.traffic
    live = counts.decode_live(mix["prompt_len"], mix["gen"])
    flops = counts.decode_step_flops(run.config, mix["batch"], live)
    return 100.0 * flops / (t * run.chips * run.peaks["bf16_flops"])
