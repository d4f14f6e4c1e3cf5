"""Share of the traced window in which the device ran no operation while
the host was in ``DecodeBatch.step``'s loop over live slots (the program's
span ``repro.decode.slots``), averaged over the chips used. At most
``device_idle_share``."""
from chipbench import program_spans as ps
from chipbench import trace


def read(run):
    sp = ps.spans(run)
    if sp is None or not run.trace.ops:
        return None
    loops = trace.union([(s.start, s.end) for s in ps.named(sp, ps.SLOTS)])
    if not loops:
        return None
    ops = run.chip_ops()
    idle = sum(ps.overlap_ns(trace.idle_gaps(o, run.trace.window), loops)
               for o in ops) / len(ops)
    return 100.0 * idle * 1e-9 / run.trace.window_s
