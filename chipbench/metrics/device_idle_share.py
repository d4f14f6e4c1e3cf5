"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""
from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    ops = run.chip_ops()
    busy = sum(trace.busy_ns(o) for o in ops) / len(ops) * 1e-9
    return 100.0 * (1.0 - busy / run.trace.window_s)
