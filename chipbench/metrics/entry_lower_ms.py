"""Host time per traced job in tracing and lowering the entry's programs
(``serve.lower.*``)."""
from chipbench import phases


def read(run):
    return phases.ms_per_job(run, "serve.lower")
