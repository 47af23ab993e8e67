"""Host time per traced job in gathering its tokens to the host and
stacking its logits (``serve.gather``)."""
from chipbench import phases


def read(run):
    return phases.ms_per_job(run, "serve.gather")
