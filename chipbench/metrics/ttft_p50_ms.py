"""Median time to first token over every request due in the window, from
its due time to the return of the serve call that delivered the token."""
import numpy as np


def read(run):
    t = run.ttft_s()
    return float(np.percentile(t, 50) * 1e3) if len(t) else None
