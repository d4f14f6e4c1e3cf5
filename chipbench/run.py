"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. Set-up makes the weights on the device from
the seed, builds ``DisaggServer`` for the cell's configuration, registers
the shared contexts and warms every shape the cell's traffic uses. The
window then drives ``serve`` open-loop for ``--seconds`` (``loop.py``).
With ``--trace 1`` the profiler records the window's last
``TRACE_SECONDS`` (from the last request's due time, where that is
earlier) and the per-layer metrics are printed; otherwise the end-to-end
ones. Once the loop has stopped, the peak device memory is read,
the server is freed and the float32 reference checks a sample of the
served tokens (``check.py``).

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``: each number compared with its limit,
which also end standard error. With no TPU, fewer chips than the cell
asks for, or no system under test in the checkout, it exits non-zero and
prints no result.

``--rehearse`` runs the same path on the CPU at the configuration's
``rehearse`` sizes, lengths cut by 16, with the kernels in interpret mode;
its line carries no metric and no device.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench" / "trace"
#: the profiler records (at least) this many seconds at the window's end
TRACE_SECONDS = 10.0
#: served tokens the reference checks per run, and most sequences
SAMPLE_TOKENS, SAMPLE_SEQS = 300, 6


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}; no result", file=sys.stderr)
    return 1


class _Tracer:
    """The profiler over the window's last ``TRACE_SECONDS``, or from the
    last request's due time where that is earlier, so that the traced part
    holds a prefill. The window's span closes at its end; the profiler
    stops once the loop has stopped, so that writing the trace out delays
    no request of the window."""

    def __init__(self, log_dir: Path, seconds: float, last_due: float):
        self.log_dir = log_dir
        self.start_at = min(seconds - TRACE_SECONDS, last_due)
        self._window = None

    def start(self):
        import jax

        from chipbench import trace
        jax.profiler.start_trace(str(self.log_dir))
        self._window = jax.profiler.TraceAnnotation(trace.WINDOW)
        self._window.__enter__()

    def end_window(self):
        self._window.__exit__(None, None, None)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def run_cell(cell, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, log=print) -> dict:
    """Set up, drive the window, check; return the result object."""
    import jax

    from chipbench import check
    from chipbench import trace as tr_mod
    from chipbench.harness import (CompileCounter, GcPauses, close_bench,
                                   finished, open_bench, window_line)
    from chipbench.loop import drive
    from chipbench.peaks import peaks
    from chipbench.readings import Run

    counter = CompileCounter()
    b = open_bench(cell, seed, seconds, rehearse)
    setup_s = time.monotonic() - T_START
    compiles0 = counter.compiles
    pauses = GcPauses()
    log_dir = TRACE_DIR / f"{cell.name}.{seed}"
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)

    reqs = b.traffic.requests
    out = drive(b.srv, reqs, seconds, b.rec,
                trace=_Tracer(log_dir, seconds, reqs[-1].due) if trace
                else None)
    in_window = counter.compiles - compiles0
    devs = jax.devices()[:cell.chips]
    peak = None if rehearse else max(
        d.memory_stats()["peak_bytes_in_use"] for d in devs)
    log(window_line(out, len(reqs)))
    log(f"compiles: {in_window} inside the window; {counter.compiles} in"
        f" the run, {counter.cache_hits} from the persistent cache;"
        f" set-up {setup_s:.3f} s; garbage collections in the window"
        f" {pauses.count}, {pauses.total * 1e3:.1f} ms, longest"
        f" {pauses.longest * 1e3:.1f} ms")

    done = finished(b.srv, reqs, out)
    sample = check.draw(done, seed, SAMPLE_TOKENS, SAMPLE_SEQS)
    m, mix = b.dims, b.mix
    close_bench(b)

    run = Run(outcome=out, setup_s=setup_s, dims=m,
              peaks=None if rehearse else peaks(devs[0].device_kind))
    if trace:
        run.trace = tr_mod.load(str(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)

    t0 = time.monotonic()
    limit = check.limit(mix)
    # no finished request to compare: not correct, and no number
    gap = check.served_gap(m, seed, sample) if sample.rids else None
    log(f"reference: {len(sample.rids)} sequences,"
        f" {sum(len(s) for s in sample.served)} served tokens checked in"
        f" {time.monotonic() - t0:.1f} s; {len(done)} of {len(reqs)}"
        f" requests finished in the loop")

    result = {"correct": check.decide(gap, limit),
              "attempted": len(reqs),
              "failed": int(out.failed.sum())}
    if rehearse:
        result["rehearsal"] = "CPU, reduced sizes: no metric, no device"
    else:
        metrics = cell.per_layer if trace else cell.end_to_end
        vals = {}
        for mt in metrics:
            v = mt.read(run)
            if v is not None:
                vals[mt.name] = {"value": v, "unit": mt.unit}
        dev = devs[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        result["metrics"] = vals
        result["device"] = device
        if trace:
            ops = run.chip_ops()
            device["busy_s"] = sum(tr_mod.busy_ns(o) for o in ops) \
                / len(ops) * 1e-9
            device["window_s"] = run.trace.window_s
            result["breakdown"] = {
                "device_ops": tr_mod.top_ops(ops[0]),
                "idle_gaps": tr_mod.idle_by_host(
                    tr_mod.idle_gaps(ops[0], run.trace.window),
                    run.trace.host)}
    result["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, reduced sizes, interpret-mode kernels; prints"
                         " no metric")
    a = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from chipbench.harness import init_jax
    from chipbench.spec import load_cell
    cell = load_cell(a.workload, ROOT)
    why = init_jax(cell.chips, a.rehearse)
    if why:
        return _fail(why)

    result = run_cell(cell, a.seed, a.seconds, bool(a.trace),
                      rehearse=a.rehearse, log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
