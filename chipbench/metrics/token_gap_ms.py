"""The serving entry's own decode spans over all decode steps of the window."""


def read(run):
    steps = sum(j.decode_steps for j in run.jobs)
    return 1e3 * sum(j.decode_s for j in run.jobs) / steps if steps else None
