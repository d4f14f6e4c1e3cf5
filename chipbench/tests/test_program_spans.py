"""The program's wall-clock spans on the trace's clock, and the readers that
use them, on a synthetic run: serve-span alignment, the count mismatch,
the cut at the window's end, self time and the idle attribution."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import program_spans as ps
from chipbench.loop import Outcome, Spans
from chipbench.readings import Run
from chipbench.spec import load_cell
from chipbench.trace import Trace

P0 = 5_000_000_000_000          # program clock (ns) at the window's start
T0 = 99_255_000_000_000         # trace clock (ns) at the traced span's start
TRACED = (1.0, 2.0)             # seconds from the window's start
#: harness serve calls: one before the profiler, two traced, one after
CALLS = [(0.4, 0.5, 1), (1.0, 1.2, 1), (1.5, 1.9, 1), (2.1, 2.2, 1)]
NEW = ("decode_slot_loop_ms", "idle_in_slot_loop_share", "sched_ms_per_req",
       "kv_host_ms_per_req", "prefill_wait_p95_ms")


def _prog():
    """The spans the program records in the three calls after the profiler
    starts; a serve span opens 2 us after its call, closes 3 us before."""
    out = []

    def span(name, a, b, parent=-1, rid=None, **args):
        out.append(SimpleNamespace(name=name, start_ns=P0 + round(a * 1e9),
                                   end_ns=P0 + round(b * 1e9), sid=len(out),
                                   parent=parent, rid=rid, args=args))
        return len(out) - 1

    s = span("repro.serve", 1.0 + 2e-6, 1.2 - 3e-6, requests=1)
    r = span("repro.runtime.run", 1.01, 1.11, s, arr=1)
    span("repro.kv.match", 1.011, 1.016, r, rid=7, matched=1024)
    span("repro.kv.gather", 1.02, 1.025, r, rid=7, reused=1024)
    span("repro.prefill", 1.03, 1.09, r, rid=7, computed=128, reused=1024)
    span("repro.decode.admit", 1.095, 1.1, r, rid=7, slot=0)
    span("repro.decode.launch", 1.12, 1.13, s, live=2)
    span("repro.decode.wait", 1.13, 1.15, s)
    span("repro.decode.slots", 1.15, 1.19, s, live=2)
    s = span("repro.serve", 1.5 + 2e-6, 1.9 - 3e-6, requests=1)
    r = span("repro.runtime.run", 1.5, 1.6, s, arr=1)
    span("repro.prefill", 1.52, 1.58, r, rid=8, computed=512, reused=0)
    span("repro.decode.slots", 1.7, 1.8, s, live=3)
    s = span("repro.serve", 2.1 + 2e-6, 2.2 - 3e-6, requests=1)
    r = span("repro.runtime.run", 2.1, 2.15, s)
    span("repro.prefill", 2.11, 2.15, r, rid=9, computed=128, reused=0)
    span("repro.decode.slots", 2.16, 2.19, s, live=3)
    return out


def _run(ops=()):
    out = Outcome(seconds=TRACED[1], due=np.zeros(0), admitted=np.zeros(0),
                  token_times=[], failed=np.zeros(0, bool),
                  reused=np.zeros(0, np.int64), prompt=np.zeros(0, np.int64),
                  spans=Spans(serve=list(CALLS)), end=2.3, trace_span=TRACED)
    tr = Trace(window=(T0, T0 + 1_000_000_000),
               ops={0: [("fusion", T0 + round(a * 1e9), T0 + round(b * 1e9))
                        for a, b in ops]})
    return Run(outcome=out, setup_s=0.0, dims={}, trace=tr)


@pytest.fixture
def prog(monkeypatch):
    spans = _prog()
    monkeypatch.setattr(ps, "recorded", lambda: spans)
    return spans


@pytest.fixture(scope="module")
def readers():
    cell = load_cell("smollm-360m.code-reuse")
    return {m.name: m.read for m in cell.per_layer}


def test_serve_spans_align_the_clocks(prog):
    sp = ps.spans(_run())
    serve = ps.named(sp, ps.SERVE)
    # the offset sits halfway between the 2 us lead and the 3 us lag
    assert serve[0].start - T0 == pytest.approx(2_000 + 500, abs=2)
    assert serve[1].start - T0 == pytest.approx(500e6 + 2_500, abs=2)
    assert [s.ns for s in serve] == [200e6 - 5_000, 400e6 - 5_000]


def test_the_cut_at_the_window_end_takes_whole_calls(prog):
    sp = ps.spans(_run())
    assert len(ps.named(sp, ps.SERVE)) == 2
    assert [s.rid for s in ps.named(sp, ps.PREFILL)] == [7, 8]
    assert all(s.start < T0 + 1_000_000_000 for s in sp)


def test_a_count_mismatch_reads_nothing(prog, readers):
    prog.pop(next(i for i, s in enumerate(prog) if s.name == ps.SERVE))
    run = _run([(0.0, 0.5)])
    assert ps.spans(run) is None
    assert all(readers[n](run) is None for n in NEW)


def test_a_serve_span_outside_its_call_reads_nothing(prog):
    prog[0].start_ns -= 10_000           # opens before its call did
    assert ps.spans(_run()) is None


def test_no_channel_and_no_trace_read_nothing(monkeypatch, readers):
    monkeypatch.setattr(ps, "recorded", lambda: None)
    assert all(readers[n](_run([(0.0, 0.5)])) is None for n in NEW)
    monkeypatch.setattr(ps, "recorded", _prog)
    run = _run()
    run.trace = None
    assert all(readers[n](run) is None for n in NEW)


def test_self_time_and_per_request_readers(prog, readers):
    run = _run()
    sp = ps.spans(run)
    # 100 ms less 75 ms of children, and 100 ms less a 60 ms prefill
    assert ps.self_ns(sp, ps.RUN) == pytest.approx(65e6, abs=2)
    assert readers["sched_ms_per_req"](run) == pytest.approx(32.5, abs=1e-5)
    assert readers["kv_host_ms_per_req"](run) == pytest.approx(7.5, abs=1e-5)
    waits = [30 - 2e-3, 20 - 2e-3]      # prefill start - serve start, ms
    assert readers["prefill_wait_p95_ms"](run) == \
        pytest.approx(float(np.percentile(waits, 95)), abs=1e-5)
    assert readers["decode_slot_loop_ms"](run) == pytest.approx(70, abs=1e-5)


def test_idle_inside_the_slot_loop(prog, readers):
    # device busy over [0, .14], [.16, .17], [.5, .75] s of the window; the
    # slot loops over [.15, .19] and [.7, .8]: idle inside them .01 + .02
    # + .05 s of the window's 1 s
    run = _run([(0.0, 0.14), (0.16, 0.17), (0.5, 0.75)])
    got = readers["idle_in_slot_loop_share"](run)
    assert got == pytest.approx(8.0, abs=1e-3)
    assert got <= readers["device_idle_share"](run) == pytest.approx(60.0)


def test_overlap_and_outermost():
    assert ps.overlap_ns([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == \
        5 + 5 + 2
    assert ps.overlap_ns([], [(0, 1)]) == 0
    sp = [ps.Span("a", 0, 9, 0, -1, None, {}),
          ps.Span("b", 1, 5, 1, 0, None, {}),
          ps.Span("c", 2, 3, 2, 1, None, {}),
          ps.Span("d", 10, 11, 3, -1, None, {})]
    assert {k: v.name for k, v in ps.outermost(sp).items()} == \
        {0: "a", 1: "a", 2: "a", 3: "d"}
