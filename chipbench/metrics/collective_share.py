"""Share of the first chip's busy time in the traced job that its collective
operations take (``chipbench/collectives.py``): the union of their
intervals over the union of every operation's."""
from chipbench import collectives


def read(run):
    coll = collectives.ops(run)
    if coll is None:
        return None
    tr = run.trace
    busy = tr.busy_s(tr.devices[0])
    return 100.0 * collectives.union_s(coll, tr.window) / busy if busy > 0 else 0.0
