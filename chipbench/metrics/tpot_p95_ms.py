"""95th percentile over requests of (last token - first token) /
(tokens - 1), counting the tokens delivered before the loop stopped."""
import numpy as np


def read(run):
    t = run.tpot_s()
    return float(np.percentile(t, 95) * 1e3) if len(t) else None
