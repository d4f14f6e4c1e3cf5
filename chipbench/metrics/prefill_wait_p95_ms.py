"""95th percentile over the traced part of the window's prefills of the
wait inside ``DisaggServer.serve`` before a request's prefill starts (the
scheduler and the prefills ahead of it): start of its ``repro.prefill``
span minus the start of the enclosing ``repro.serve``."""
import numpy as np

from chipbench import program_spans as ps


def read(run):
    sp = ps.spans(run)
    if sp is None:
        return None
    top = ps.outermost(sp)
    waits = [s.start - top[s.sid].start for s in ps.named(sp, ps.PREFILL)
             if top[s.sid].name == ps.SERVE]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e-6
