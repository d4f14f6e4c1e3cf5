"""Model FLOP/s utilisation of the traced window: the FLOPs the model needs
for the prompt tokens computed and the tokens decoded there (model heads,
no dead decode lanes, no reused tokens), over window x chips x peak."""
from chipbench import flops


def read(run):
    if run.trace is None:
        return None
    m, sp = run.dims, run.outcome.spans
    need = sum(flops.prefill(m, q, p)
               for _, _, (q, p) in run.traced_calls(sp.prefill))
    need += sum(flops.decode(m, pos) for _, _, positions in
                run.traced_calls(sp.step) for pos in positions)
    chips = len(run.trace.ops)
    if not need or not chips:
        return None
    return 100.0 * need / (run.trace.window_s * chips
                           * run.peaks["flops_bf16"])
