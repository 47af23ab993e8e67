"""Programs traced and lowered per traced job (``serve.lower.*`` spans)."""
from chipbench import phases


def read(run):
    return phases.per_job(run, "serve.lower")
