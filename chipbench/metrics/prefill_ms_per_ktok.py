"""Wall time in ``ServingEngine.prefill`` per 1,000 prompt tokens computed
(reused tokens excluded), over the window's prefills."""


def read(run):
    calls = run.window_calls(run.outcome.spans.prefill)
    tokens = sum(c[2][0] for c in calls)
    if not tokens:
        return None
    return sum(e - s for s, e, _ in calls) / tokens * 1e6
