"""Time the first chip spends in collective operations inside one call of
the decode program (the union of their intervals in the call), on average."""
from chipbench import collectives, programs


def read(run):
    coll, calls = collectives.ops(run), programs.calls(run, programs.DECODE)
    if coll is None or not calls:
        return None
    return 1e3 * sum(collectives.union_s(coll, (c.start, c.end)) for c in calls) / len(calls)
