"""The program's own wall-clock spans of a traced run, on the trace's clock.

While the profiler runs, the program records spans (``repro.serve``,
``repro.runtime.run``, ``repro.kv.*``, ``repro.prefill``,
``repro.decode.*``) into its process-wide channel
``repro.core.telemetry.wall_spans``, on ``time.perf_counter_ns()``. They
cover the traced part of the window. ``spans(run)`` puts them on the
trace's clock in two steps:

1. program clock -> harness clock: the program's ``repro.serve`` spans
   are matched one for one, in order, with the loop's ``serve`` calls that
   started at or after ``outcome.trace_span[0]``. Both read the same
   monotonic clock, so one offset maps the one onto the other; it lies
   between the latest (span end - call end) and the earliest (span start -
   call start), and is taken halfway.
2. harness clock -> trace clock: ``trace.window[0]`` is
   ``outcome.trace_span[0]`` (the window's annotation opens immediately
   before that reading).

The spans of the ``serve`` calls that start before ``trace_span[1]`` are
kept, each call's whole tree, so that a span's self time never counts a
child cut off at the window's end. Where the counts
differ, where no offset fits every pair, or where the program has no such
channel (a commit before it), ``spans`` is None and so is every reader: it
never guesses.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.trace import Interval

SERVE = "repro.serve"
RUN = "repro.runtime.run"
PREFILL = "repro.prefill"
SLOTS = "repro.decode.slots"
#: the prefix index and paged KV on the host, and the decode hand-off
KV = ("repro.kv.match", "repro.kv.gather", "repro.kv.register",
      "repro.decode.admit")


@dataclass(frozen=True)
class Span:
    name: str
    start: int                 # ns, trace clock
    end: int
    sid: int
    parent: int                # sid of the enclosing span, -1 at the top
    rid: Optional[int]
    args: Dict[str, int]

    @property
    def ns(self) -> int:
        return self.end - self.start


def recorded() -> Optional[list]:
    """The spans the program recorded in this process; None where the
    program has no wall-clock channel."""
    try:
        from repro.core.telemetry import wall_spans
    except ImportError:
        return None
    return list(wall_spans.spans)


def align(prog: Sequence, serve_calls: Sequence[Tuple[float, float, int]],
          trace_span: Tuple[float, float], window: Interval
          ) -> Optional[List[Span]]:
    """``prog``'s spans (``name``, ``start_ns``, ``end_ns``, ``sid``,
    ``parent``, ``rid``, ``args``) on the trace's clock, given the loop's
    ``serve`` calls and traced span (seconds from the window's start) and
    the trace's window (ns)."""
    a, b = trace_span
    calls = [c for c in serve_calls if c[0] >= a]
    serves = [s for s in prog if s.name == SERVE]
    if not calls or len(calls) != len(serves):
        return None
    lo = max(s.end_ns - c[1] * 1e9 for s, c in zip(serves, calls))
    hi = min(s.start_ns - c[0] * 1e9 for s, c in zip(serves, calls))
    if lo > hi:
        return None
    zero = (lo + hi) / 2          # program ns at the window's start
    shift = window[0] - a * 1e9 - zero
    top = outermost(prog)
    return [Span(s.name, round(s.start_ns + shift), round(s.end_ns + shift),
                 s.sid, s.parent, s.rid, dict(s.args))
            for s in prog if top[s.sid].start_ns - zero < b * 1e9]


def spans(run) -> Optional[List[Span]]:
    """The program's spans of a ``--trace 1`` run on the trace's clock, or
    None (see the module docstring)."""
    o = run.outcome
    if run.trace is None or o.trace_span is None:
        return None
    prog = recorded()
    if prog is None:
        return None
    return align(prog, o.spans.serve, o.trace_span, run.trace.window)


def named(sp: Iterable[Span], *names: str) -> List[Span]:
    return [s for s in sp if s.name in names]


def self_ns(sp: List[Span], of: str) -> int:
    """Self time of the spans named ``of``: their durations minus those of
    their direct children (spans of one thread nest without overlap)."""
    sids = {s.sid for s in sp if s.name == of}
    own = sum(s.ns for s in sp if s.sid in sids)
    return own - sum(s.ns for s in sp if s.parent in sids)


def outermost(sp: Sequence) -> Dict[int, Any]:
    """sid -> the outermost span around it (itself at the top); ``sp`` in
    the order the spans opened, so a parent comes before its children."""
    top: Dict[int, Any] = {}
    for s in sp:
        top[s.sid] = top.get(s.parent, s)
    return top


def overlap_ns(xs: List[Interval], ys: List[Interval]) -> int:
    """Total overlap of two lists of disjoint intervals, each sorted."""
    tot, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot
