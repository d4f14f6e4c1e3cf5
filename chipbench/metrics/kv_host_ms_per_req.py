"""Host time of the KV data plane per prefilled request: the program's
spans ``repro.kv.match`` (prefix index), ``repro.kv.gather`` (page gather),
``repro.kv.register`` (page put and index insert) and
``repro.decode.admit`` (the cache written into a decode slot), over the
``repro.prefill`` spans of the traced part of the window."""
from chipbench import program_spans as ps


def read(run):
    sp = ps.spans(run)
    n = len(ps.named(sp or [], ps.PREFILL))
    if not n:
        return None
    return sum(s.ns for s in ps.named(sp, *ps.KV)) / n * 1e-6
