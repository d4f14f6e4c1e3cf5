"""Wall time of the host loop over live slots in ``DecodeBatch.step`` (the
program's span ``repro.decode.slots``: a token read back, two updates
dispatched and the bookkeeping, per live slot), mean per decode step with
live slots (a step with none opens no span), over the traced part of the
window."""
from chipbench import program_spans as ps


def read(run):
    loops = ps.named(ps.spans(run) or [], ps.SLOTS)
    if not loops:
        return None
    return sum(s.ns for s in loops) / len(loops) * 1e-6
