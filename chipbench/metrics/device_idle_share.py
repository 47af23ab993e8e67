"""Share of the traced job in which no operation ran on the first chip."""


def read(run):
    if run.trace is None or not run.trace.devices or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s(run.trace.devices[0]) / run.trace.window_s)
