"""Output tokens (first tokens included) delivered inside the window, over
the window; tokens of the drain after it do not count."""


def read(run):
    n = run.tokens_in_window()
    return n / run.outcome.seconds if n else None
