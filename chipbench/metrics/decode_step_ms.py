"""Wall time per ``DecodeBatch.step`` call with live slots, over the
window."""


def read(run):
    calls = run.window_calls(run.outcome.spans.step)
    if not calls:
        return None
    return sum(e - s for s, e, _ in calls) / len(calls) * 1e3
