"""The open-loop driver and the harness's spans around each layer.

Each tick calls ``DisaggServer.serve(due, decode_steps=1)``: admit the
requests that are due, prefill them, then decode one token for every live
slot. With nothing due and live slots it calls ``serve([])``; with nothing
due and no live slot it sleeps until the next due time. A request is
stamped on the virtual clock at ``max(due, runtime now)`` and timed from its
due time, so a tick that blocks counts against every request waiting on it.
After the window the loop goes on, with no new arrivals, until every
request due in it has its first token.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving import ServeRequest


@dataclass
class Spans:
    """Host-clock spans of the calls into each layer, in seconds from the
    window's start: (start, end, what)."""

    serve: List[Tuple[float, float, int]] = field(default_factory=list)
    prefill: List[Tuple[float, float, Tuple[int, int]]] = \
        field(default_factory=list)        # (tokens computed, prefix reused)
    step: List[Tuple[float, float, Tuple[int, ...]]] = \
        field(default_factory=list)        # positions written, one per slot
    idle: List[Tuple[float, float, float]] = \
        field(default_factory=list)        # sleeps, with the wake-up asked


class Recorder:
    """Wraps ``srv.engine.prefill`` and ``srv.decoder.step`` on the
    instance: times each call, marks it in the profiler's trace, and keeps
    the tokens each decode step returned."""

    def __init__(self, srv, clock: Callable[[], float] = time.perf_counter):
        self.spans = Spans()
        self.clock = clock
        self.t0 = clock()
        self.stepped: Dict[int, int] = {}
        prefill, step, dec = srv.engine.prefill, srv.decoder.step, srv.decoder

        def timed_prefill(tokens, prefix_cache=None, prefix_len=0,
                          extra=None):
            t0 = self.now()
            with jax.profiler.TraceAnnotation("chipbench.prefill"):
                out = prefill(tokens, prefix_cache=prefix_cache,
                              prefix_len=prefix_len, extra=extra)
            reused = prefix_len if prefix_cache is not None else 0
            self.spans.prefill.append(
                (t0, self.now(), (len(tokens) - reused, reused)))
            return out

        def timed_step():
            pos = tuple(s.pos for s in dec.slots.values())
            t0 = self.now()
            with jax.profiler.TraceAnnotation("chipbench.decode_step"):
                out = step()
            if pos:
                self.spans.step.append((t0, self.now(), pos))
            self.stepped.update(out)
            return out

        srv.engine.prefill = timed_prefill
        srv.decoder.step = timed_step

    def now(self) -> float:
        """Seconds since ``t0`` (the window's start, once it opens)."""
        return self.clock() - self.t0


@dataclass
class Outcome:
    """What the window did to each request (times in seconds from the
    window's start)."""

    seconds: float
    due: np.ndarray
    admitted: np.ndarray            # start of the serve call that took it
    token_times: List[List[float]]  # first token, then each decoded one
    failed: np.ndarray              # shed, or refused a decode slot
    reused: np.ndarray
    prompt: np.ndarray
    spans: Spans
    end: float                      # when the loop stopped
    trace_span: Optional[Tuple[float, float]] = None


def drive(srv, requests, seconds: float, rec: Recorder, trace=None
          ) -> Outcome:
    """Run the window. ``trace``, for a traced run: ``.start_at`` (seconds
    from the window's start), ``.start()``, ``.end_window()`` (called at
    the window's end) and ``.stop()`` (called once the loop stops)."""
    n = len(requests)
    due = np.asarray([r.due for r in requests])
    admitted = np.full(n, np.nan)
    times: List[List[float]] = [[] for _ in range(n)]
    failed = np.zeros(n, bool)
    reused = np.zeros(n, np.int64)
    by_rid = {r.rid: i for i, r in enumerate(requests)}
    rt = srv.runtime
    trace_span, tracing = None, False
    nxt = 0
    rec.t0 = rec.clock()
    rec.spans = Spans()
    clock = rec.now
    while True:
        now = clock()
        if trace is not None and not tracing and trace_span is None \
                and now >= trace.start_at:
            trace.start()
            tracing, trace_span = True, (clock(), None)
        if tracing and now >= seconds:
            trace.end_window()
            tracing = False
            trace_span = (trace_span[0], now)
        if nxt >= n and now >= seconds:
            if trace is not None:
                trace.stop()
            break
        take = []
        while nxt < n and due[nxt] <= now:
            take.append(nxt)
            nxt += 1
        if not take and not srv.decoder.n_active:
            wake = due[nxt] if nxt < n else seconds
            with jax.profiler.TraceAnnotation("chipbench.idle"):
                time.sleep(max(0.0, wake - now))
            rec.spans.idle.append((now, clock(), wake))
            continue
        vnow = max(rt.evq.now, rt.net.now)
        batch = [ServeRequest(rid=requests[i].rid,
                              arrival=max(float(due[i]), vnow),
                              tokens=requests[i].tokens,
                              max_new=requests[i].max_new) for i in take]
        rec.stepped = {}
        ta = clock()
        with jax.profiler.TraceAnnotation("chipbench.serve"):
            res = srv.serve(batch, decode_steps=1)
        tr = clock()
        rec.spans.serve.append((ta, tr, len(take)))
        for i, r in zip(take, res):
            admitted[i] = ta
            failed[i] = r.shed or not r.decode_admitted
            reused[i] = r.reused_tokens
            if not r.shed:
                times[i].append(tr)
        for rid in rec.stepped:
            i = by_rid.get(rid)
            if i is not None:
                times[i].append(tr)
    return Outcome(seconds=seconds, due=due, admitted=admitted,
                   token_times=times, failed=failed, reused=reused,
                   prompt=np.asarray([len(r.tokens) for r in requests]),
                   spans=rec.spans, end=clock(), trace_span=trace_span)


def quiesce(srv) -> None:
    """Decode until no slot is live (set-up and between sweep rates)."""
    while srv.decoder.n_active:
        srv.serve([], decode_steps=1)
