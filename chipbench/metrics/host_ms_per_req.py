"""Host time of the runtime and data plane per admitted request: wall time
in ``DisaggServer.serve`` minus the time in ``engine.prefill`` and
``decoder.step`` (the MFS runtime's event loop, routing, the prefix index
and page pool, ``DecodeBatch.add``), over the calls of the window."""


def read(run):
    sp = run.outcome.spans
    serve = run.window_calls(sp.serve)
    admitted = sum(c[2] for c in serve)
    if not admitted:
        return None
    inner = sum(e - s for s, e, _ in run.window_calls(sp.prefill)) + \
        sum(e - s for s, e, _ in run.window_calls(sp.step))
    return (sum(e - s for s, e, _ in serve) - inner) / admitted * 1e3
