"""95th percentile of time to first token over every request due in the
window (a request that never got a first token counts as infinite)."""
import numpy as np


def read(run):
    t = run.ttft_s()
    return float(np.percentile(t, 95) * 1e3) if len(t) else None
