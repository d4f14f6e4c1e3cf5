"""Find a cell's knee: one server, one window per offered rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 1,2,3

For each rate, lowest first, a server set up as ``run.py`` sets one up
(so every window starts with no live slot), the cell's traffic at that
rate for ``--seconds``, and a line of what it did:
requests failed (shed or refused a decode slot), how late the loop
admitted them (first and last quarter of the window: a backlog that grows
shows as the second above the first), TTFT, TPOT and output tokens/s.
The knee is the highest rate with no failed request and no growing
backlog; the cell runs at 0.8x of it. Slots are drained between rates.
``--slots`` replaces the configuration's ``decode_slots``, to find how many
fit: each line also gives the device's peak memory so far.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import init_jax, window_line     # noqa: E402
from chipbench.spec import load_cell                    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    cell = load_cell(a.workload)
    if a.slots:
        conf = cell.config
        cell = dataclasses.replace(cell, config=dict(conf, serving=dict(
            conf["serving"], decode_slots=a.slots)))
    why = init_jax(cell.chips, a.rehearse)
    if why:
        print(f"sweep: {why}", file=sys.stderr)
        return 1

    import jax
    import numpy as np

    from chipbench.harness import close_bench, open_bench, sizes
    from chipbench.loop import drive
    from chipbench.readings import Run

    rates = sorted(float(r) for r in a.rates.split(","))
    for rate in rates:
        mix = dict(sizes(cell, a.rehearse)[1], rate=rate)
        b = open_bench(cell, a.seed, a.seconds, a.rehearse, mix=mix)
        tr = b.traffic
        out = drive(b.srv, tr.requests, a.seconds, b.rec)
        close_bench(b)
        run = Run(outcome=out, setup_s=0.0, dims=None)
        late = out.admitted - out.due
        q = max(1, len(late) // 4)
        row = {
            "rate": rate, "attempted": len(tr.requests),
            "failed": int(out.failed.sum()),
            "late_first_q_ms": float(np.nanmean(late[:q]) * 1e3),
            "late_last_q_ms": float(np.nanmean(late[-q:]) * 1e3),
            "ttft_p50_ms": float(np.percentile(run.ttft_s(), 50) * 1e3),
            "ttft_p95_ms": float(np.percentile(run.ttft_s(), 95) * 1e3),
            "tpot_p95_ms": float(np.percentile(run.tpot_s(), 95) * 1e3)
            if len(run.tpot_s()) else None,
            "output_tokens_per_s": run.tokens_in_window() / a.seconds,
            "decode_step_ms": float(np.mean([e - s for s, e, _ in
                                             out.spans.step]) * 1e3)
            if out.spans.step else None,
            "slots": cell.config["serving"]["decode_slots"],
            "memory_peak_bytes": None if a.rehearse else
            jax.devices()[0].memory_stats()["peak_bytes_in_use"],
        }
        print(window_line(out, len(tr.requests)), flush=True)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
