"""The collective operations of the first chip in the traced job.

On a TPU the trace names each operation by its HLO text,
``%<name> = <shape> <opcode>(<operands>), ..., calls=%<computation>``. A
collective shows there in three forms, all classed here by the
operation's own name or by the computation it calls, never by its
operands (an ``add`` of an all-reduce's result is not a collective):

- a plain one: ``%all-reduce.19``, ``%all-gather.7``, ``%reduce-scatter.2``,
  ``%all-to-all.1``, ``%collective-permute.3``;
- the two ends of an asynchronous one: ``%all-reduce-start.1`` /
  ``%all-reduce-done.1``, ``%collective-permute-start`` /
  ``-done``, and the compiler's ``%async-collective-start.3`` /
  ``%async-collective-done.3`` fusions;
- a fusion that does a collective's work beside other work:
  ``%fusion.153 = ... calls=%async_collective_fusion.153``, or
  ``calls=%all-reduce-scatter.1``.

Only the operations' own intervals on the "XLA Ops" line count: the data an
asynchronous collective moves between its start and its done, while other
operations run (the trace's "Async XLA Ops" line), is not time the chip's
stream of operations waits for it.
"""
from __future__ import annotations

import re

from chipbench import trace as trace_lib

_KINDS = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
_OWN = re.compile(rf"^%?(?:{_KINDS}|async-collective|all-reduce-scatter)(?:-start|-done|-fusion)?"
                  r"(?:\.[\w.-]*)?(?:[\s=]|$)")
_CALLS = re.compile(rf"calls=%?(?:{_KINDS}|async_collective_fusion|all-reduce-scatter)\b")


def is_collective(op_name: str) -> bool:
    return bool(_OWN.match(op_name) or _CALLS.search(op_name))


def ops(run) -> list[trace_lib.Span] | None:
    """Chip 0's collective operations, or None where there is no device trace."""
    if run.trace is None or not run.trace.devices:
        return None
    return [o for o in run.trace.devices[0].ops if is_collective(o.name)]


def union_s(spans: list[trace_lib.Span], window: tuple[float, float]) -> float:
    return trace_lib.union_ns(spans, window) * 1e-9

