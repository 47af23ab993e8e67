"""Host time to dispatch one decode step (``serve.decode_step``: the copy of
``cache_len`` and the call), mean over the traced job's steps. Once the
device's queue is full the call waits for a step to finish, so in a long
device-bound decode this reads the device's step time, not host cost."""
from chipbench import phases


def read(run):
    js = phases.jobs(run)
    if not js:
        return None
    steps = [s for j in js for s in phases.spans(run, "serve.decode_step", j)]
    return 1e-6 * sum(s.dur for s in steps) / len(steps) if steps else 0.0
