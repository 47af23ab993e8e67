"""Host time per traced job in compiling the lowered programs
(``serve.compile.*``): a load from the persistent cache once it is warm."""
from chipbench import phases


def read(run):
    return phases.ms_per_job(run, "serve.compile")
