"""Readings that set a cell's correctness limit.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,... --control-seeds 1,2,3

For each seed, in one process: set up as ``run.py`` does, drive the
cell's traffic for ``--seconds``, decode until every request has all its
tokens, draw the sample as a run does (the longest finished request in
it), free the server, and read the widest gap of a served token below the
float32 reference's best (``check.served_gap``). For the control seeds
also read the control: the gap of the float8 reference's first choice at
the same positions (``check.control_gap``). One JSON line per seed, with
``correct`` and, for a control seed, ``control_correct``: each reading
decided against the workload's limit as a run decides it
(``check.decide``); the control has to come out not correct.

The lower reading is the largest served gap over a dozen seeds or more,
the upper the smallest control gap; the limit in ``workloads/<cell>.json``
lies between them (``PERF.md`` gives the readings).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import init_jax                  # noqa: E402
from chipbench.spec import load_cell                    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    cell = load_cell(a.workload)
    why = init_jax(cell.chips, a.rehearse)
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 1

    from chipbench import check
    from chipbench.harness import (close_bench, finished, open_bench,
                                   window_line)
    from chipbench.loop import drive, quiesce
    from chipbench.run import SAMPLE_SEQS, SAMPLE_TOKENS

    ctl = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.monotonic()
        b = open_bench(cell, seed, a.seconds, a.rehearse)
        reqs = b.traffic.requests
        out = drive(b.srv, reqs, a.seconds, b.rec)
        quiesce(b.srv)
        done = finished(b.srv, reqs, out)
        print(window_line(out, len(reqs)), flush=True)
        m, limit = b.dims, check.limit(b.mix)
        close_bench(b)
        sample = check.draw(done, seed, SAMPLE_TOKENS, SAMPLE_SEQS)
        row = {"seed": seed, "sequences": len(sample.rids),
               "tokens": int(sum(len(s) for s in sample.served)),
               "longest": int(max(len(s) for s in sample.served)),
               "served_gap": check.served_gap(m, seed, sample),
               "limit": limit}
        row["correct"] = check.decide(row["served_gap"], limit)
        if seed in ctl:
            row["control_gap"] = check.control_gap(m, seed, sample)
            row["control_correct"] = check.decide(row["control_gap"], limit)
        row["seconds"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
