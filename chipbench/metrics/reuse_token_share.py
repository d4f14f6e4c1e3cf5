"""Share of the prompt tokens of the window's requests that the prefix
index served from reused pages (``ServeResult.reused_tokens``)."""


def read(run):
    o = run.outcome
    total = int(o.prompt.sum())
    return 100.0 * int(o.reused.sum()) / total if total else None
