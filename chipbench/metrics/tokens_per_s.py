"""Generated tokens of every job in the window over the window's wall time."""


def read(run):
    return sum(j.batch * j.gen for j in run.jobs) / run.window_s
