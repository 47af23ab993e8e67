"""The serving entry's own prefill span (call to first tokens on the host),
mean over the window's jobs; every request of a static batch shares it."""


def read(run):
    return 1e3 * sum(j.prefill_s for j in run.jobs) / len(run.jobs)
