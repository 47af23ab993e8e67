"""Process start to the end of the warm-up job at the cell's shapes."""


def read(run):
    return run.setup_s
