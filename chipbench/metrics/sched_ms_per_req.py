"""Host time of the MFS runtime's event loop (``MsFlowRuntime.run``: the
router, RMLQ, Algorithm 1, the fluid network) per prefilled request: the
self time of the program's ``repro.runtime.run`` spans, their durations
less the data-plane and prefill spans inside them, over the
``repro.prefill`` spans of the traced part of the window."""
from chipbench import program_spans as ps


def read(run):
    sp = ps.spans(run)
    n = len(ps.named(sp or [], ps.PREFILL))
    if not n:
        return None
    return ps.self_ns(sp, ps.RUN) / n * 1e-6
