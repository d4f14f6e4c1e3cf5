"""Share of its roofline that the prefill flash-attention kernel reached
in the traced window: the least time the chip needs for the attention of
the traced prefills (model heads, causal mask; ``flops.flash_call`` per
layer) over the device time of the kernel's events."""
from chipbench import flops, trace


def read(run):
    if run.trace is None:
        return None
    m, pk = run.dims, run.peaks
    least = 0.0
    for _, _, (q_len, prefix) in run.traced_calls(run.outcome.spans.prefill):
        f, b = flops.flash_call(m, q_len, prefix)
        least += m["n_layers"] * max(f / pk["flops_bf16"],
                                     b / pk["hbm_bytes_per_s"])
    spent = sum(trace.by_name(ops, trace.FLASH)[1]
                for ops in run.chip_ops()) * 1e-9
    if not least or not spent:
        return None
    return 100.0 * least / spent
