"""What a metric reader is handed: one run's outcome, spans and trace."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from .loop import Outcome
from .trace import Trace


@dataclass
class Run:
    outcome: Outcome
    setup_s: float
    dims: Dict                     # model.dims of the configuration
    peaks: Optional[Dict] = None   # peaks.peaks of the chip
    trace: Optional[Trace] = None  # --trace 1 only

    # ------------------------------------------------------- requests
    def ttft_s(self) -> np.ndarray:
        """Due time to first token, per request due in the window; a
        request that never got one reads infinity."""
        o = self.outcome
        first = np.asarray([t[0] if t else np.inf for t in o.token_times])
        return first - o.due

    def tpot_s(self) -> np.ndarray:
        """(last token - first token) / (tokens - 1), per request with two
        tokens or more delivered before the loop stopped."""
        return np.asarray([(t[-1] - t[0]) / (len(t) - 1)
                           for t in self.outcome.token_times if len(t) > 1])

    def tokens_in_window(self) -> int:
        s = self.outcome.seconds
        return sum(sum(1 for x in t if x <= s)
                   for t in self.outcome.token_times)

    # ---------------------------------------------------------- spans
    def window_calls(self, spans: List[Tuple]) -> List[Tuple]:
        """Calls that started inside the window."""
        return [c for c in spans if c[0] < self.outcome.seconds]

    def traced_calls(self, spans: List[Tuple]) -> List[Tuple]:
        """Calls wholly inside the traced part of the window (the loop
        starts and stops the profiler between calls)."""
        a, b = self.outcome.trace_span
        return [c for c in spans if c[0] >= a and c[1] <= b]

    # ----------------------------------------------------------- trace
    def chip_ops(self) -> List[List[Tuple[str, int, int]]]:
        """Device operations inside the traced window, per chip used."""
        return [self.trace.clipped(c) for c in sorted(self.trace.ops)]
