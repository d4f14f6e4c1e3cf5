"""Reduce a JAX profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the device
operations of each chip (line ``XLA Ops`` of every ``/device:TPU:<n>``
plane) and the harness's own host spans (``TraceAnnotation`` names that
start with ``chipbench.``, on the host plane), all on the trace's clock.
The window is the span ``chipbench.window``. The rest are pure functions
of event lists, checked in ``tests/test_trace.py``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

WINDOW = "chipbench.window"
#: the prefill attention kernel: the ``pallas_call`` inside
#: ``repro.kernels.flash_attention.flash_attention``
FLASH = re.compile(r"^flash_attention(\.\d+)?$")
#: ops whose events span the ops of their body
CONTAINERS = {"while", "conditional", "call"}


@dataclass
class Trace:
    window: Interval
    ops: Dict[int, List[Tuple[str, int, int]]]    # chip -> (name, start, end)
    host: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clipped(self, chip: int) -> List[Tuple[str, int, int]]:
        a, b = self.window
        return [(n, max(s, a), min(e, b)) for n, s, e in self.ops[chip]
                if e > a and s < b]


def records(log_dir: str) -> Iterator[Tuple[str, str, str, int, int]]:
    """(plane, line, event name, start ns, end ns) of every event of the
    newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, int(e.start_ns),
                       int(e.end_ns))


def from_records(recs: Iterable[Tuple[str, str, str, int, int]]) -> Trace:
    """Device ops (line ``XLA Ops`` of each TPU) and harness spans."""
    ops: Dict[int, List] = {}
    host: List = []
    for plane, line, name, s, e in recs:
        dev = re.match(r"^/device:TPU:(\d+)$", plane)
        if dev and line == "XLA Ops":
            ops.setdefault(int(dev.group(1)), []).append((op_name(name), s, e))
        elif plane.startswith("/host:") and name.startswith("chipbench."):
            host.append((name, s, e))
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    return Trace(window=wins[0], ops=ops,
                 host=[h for h in host if h[0] != WINDOW])


def load(log_dir: str) -> Trace:
    return from_records(records(log_dir))


def op_name(event: str) -> str:
    """The HLO instruction's name: a TPU trace names each op event by its
    whole instruction (``%flash_attention.3 = bf16[...] custom-call(...)``).
    """
    return event.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: List[Tuple[str, int, int]]) -> int:
    return sum(e - s for s, e in union([(s, e) for _, s, e in ops]))


def idle_gaps(ops: List[Tuple[str, int, int]],
              window: Interval) -> List[Interval]:
    """Stretches of the window in which no operation ran."""
    gaps, t = [], window[0]
    for s, e in union([(s, e) for _, s, e in ops]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def by_name(ops: List[Tuple[str, int, int]], pattern: re.Pattern
            ) -> Tuple[int, int]:
    """(count, total ns) of the operations whose name matches."""
    hits = [e - s for n, s, e in ops if pattern.match(n)]
    return len(hits), sum(hits)


def top_ops(ops: List[Tuple[str, int, int]], k: int = 10
            ) -> List[Tuple[str, float]]:
    """Operations by total device time, numbered instances merged; loops
    and calls, whose events hold the operations inside them, left out."""
    tot: Dict[str, int] = defaultdict(int)
    for n, s, e in ops:
        base = re.sub(r"\.\d+$", "", n)
        if base not in CONTAINERS:
            tot[base] += e - s
    return [(n, t * 1e-9) for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]


def host_activity(host: List[Tuple[str, int, int]], starts: List[int],
                  t: int, depth: int = 64) -> str:
    """The innermost harness span running at ``t`` (``host`` sorted by
    start, ``starts`` their starts): the latest-started span that holds
    ``t``; spans nest, so a short look back finds it. "untraced" where
    the host was in none."""
    i = bisect.bisect_right(starts, t)
    for name, s, e in reversed(host[max(0, i - depth):i]):
        if e > t:
            return name
    return "untraced"


def idle_by_host(gaps: List[Interval], host: List[Tuple[str, int, int]],
                 k: int = 10) -> List[Tuple[str, float]]:
    """Idle device time summed by what the host was doing at each gap's
    midpoint, largest first."""
    host = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in host]
    tot: Dict[str, int] = defaultdict(int)
    for s, e in gaps:
        tot[host_activity(host, starts, (s + e) // 2)] += e - s
    return [(n, t * 1e-9) for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]
