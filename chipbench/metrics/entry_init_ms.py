"""Host time per traced job in placing the weights, caches and prompt
(``serve.init``); the device work it starts runs on past the span."""
from chipbench import phases


def read(run):
    return phases.ms_per_job(run, "serve.init")
