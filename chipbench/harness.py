"""Set-up shared by the benchmark run, the rate sweep and the control."""
from __future__ import annotations

import gc
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
#: rehearsal sizes: lengths and outputs cut by these factors
REHEARSE_LEN_DIV, REHEARSE_OUT_DIV = 16, 8


def init_jax(chips: int, rehearse: bool) -> Optional[str]:
    """Import JAX for a run; the reason it cannot run, or None.

    A run keeps JAX's persistent compilation cache at a fixed path inside
    the checkout, and caches every program, the small eager ones too.
    ``rehearse``: the CPU, with the Pallas kernels in interpret mode."""
    if not (ROOT / "src" / "repro").is_dir():
        return f"no system under test (src/repro) in {ROOT}"
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        return None
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not under /tmp
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.default_backend() != "tpu":
        return f"no TPU (JAX found {jax.default_backend()!r})"
    if len(jax.devices()) < chips:
        return f"needs {chips} chips, JAX found {len(jax.devices())}"
    return None


class CompileCounter:
    """XLA compiles (persistent-cache hits included) and cache hits, via
    ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcPauses:
    """Pauses of Python's garbage collector, via ``gc.callbacks``."""

    def __init__(self):
        self.count, self.total, self.longest = 0, 0.0, 0.0
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.count, self.total = self.count + 1, self.total + d
            self.longest = max(self.longest, d)


@dataclass
class Bench:
    """One server of a cell, warmed, with its traffic."""

    mix: Dict[str, Any]
    dims: Dict[str, Any]
    srv: Any
    rec: Any
    traffic: Any


def sizes(cell, rehearse: bool):
    """(configuration, mix, dims) as run; checked against each other."""
    from chipbench import model, traffic
    conf, mix = cell.config, cell.traffic
    if rehearse:
        conf = model.shrunk(conf)
        mix = traffic.scaled(mix, REHEARSE_LEN_DIV, REHEARSE_OUT_DIV)
    m = model.dims(conf)
    need = traffic.max_tokens(mix)
    if need + 2 > conf["serving"]["decode_capacity"] or need > m["max_len"]:
        raise ValueError(f"{cell.name}: requests of {need} tokens exceed the"
                         f" decode capacity or the model's {m['max_len']}")
    return conf, mix, m


def open_bench(cell, seed: int, seconds: float, rehearse: bool,
               mix: Optional[Dict] = None) -> Bench:
    """Weights from the seed, the server, the traffic of a window of
    ``seconds``, and every shape of it warmed."""
    from chipbench import model, traffic
    from chipbench.loop import Recorder
    from chipbench.reference import params_key

    conf, cell_mix, m = sizes(cell, rehearse)
    mix = mix or cell_mix
    hw = None
    if rehearse:
        from repro.simcluster.hw import TPU_V5E
        hw = TPU_V5E
    _, _, srv = model.build_server(conf, params_key(seed),
                                   mix.get("policy", "mfs"), hw=hw)
    tr = traffic.generate(mix, seed, seconds, m["vocab"])
    rec = Recorder(srv)
    warm_up(srv, tr)
    # set-up's garbage is collected now and kept out of later collections,
    # so that the window does not pay for it
    gc.collect()
    gc.freeze()
    return Bench(mix=mix, dims=m, srv=srv, rec=rec, traffic=tr)


def close_bench(b: Bench) -> None:
    """Free the server and its device buffers. Set-up froze its objects out
    of garbage collection (``open_bench``), and the server holds reference
    cycles (the recorder's wrappers), so they are unfrozen and collected."""
    b.srv = b.rec = None
    gc.unfreeze()
    gc.collect()


def warm_up(srv, tr) -> None:
    """Register the shared contexts, then serve one request of every shape
    the window will use, and decode until no slot is live."""
    from repro.serving import ServeRequest

    from chipbench.loop import quiesce

    def now():
        return max(srv.runtime.evq.now, srv.runtime.net.now)

    if tr.contexts:
        t = now()
        srv.serve([ServeRequest(rid=-10_000 - i, arrival=t, tokens=c,
                                max_new=1)
                   for i, c in enumerate(tr.contexts)], decode_steps=1)
        quiesce(srv)
    t = now()
    res = srv.serve([ServeRequest(rid=r.rid, arrival=t, tokens=r.tokens,
                                  max_new=r.max_new) for r in tr.warmup],
                    decode_steps=1)
    quiesce(srv)
    if tr.contexts and not all(r.reused_tokens for r in res):
        raise RuntimeError("a warm-up request did not reuse its context")


def finished(srv, requests: List, out) -> Dict[int, tuple]:
    """rid -> (prompt, served tokens) of the requests that got all their
    tokens and were not failed."""
    done = {}
    for i, r in enumerate(requests):
        res = srv.results.get(r.rid)
        if res is not None and not out.failed[i] \
                and len(res.tokens) == r.max_new:
            done[r.rid] = (r.tokens, list(res.tokens))
    return done


def longest_call(spans) -> str:
    """The longest ``serve`` call of the window and what it spent in the
    prefill engine and the decode step, and the longest time between two
    calls: where a stall of the loop sits."""
    if not spans.serve:
        return "no serve call"

    def inside(xs, a, b):
        return sum(e - s for s, e, _ in xs if a <= s and e <= b)

    a, b, k = max(spans.serve, key=lambda c: c[1] - c[0])
    between = max((n[0] - c[1] - inside(spans.idle, c[1], n[0])
                   for c, n in zip(spans.serve, spans.serve[1:])),
                  default=0.0)
    return (f"longest serve call {(b - a) * 1e3:.1f} ms at {a:.3f} s"
            f" ({k} admitted; prefill {inside(spans.prefill, a, b) * 1e3:.1f}"
            f" ms, decode step {inside(spans.step, a, b) * 1e3:.1f} ms);"
            f" longest time between calls, sleeps left out,"
            f" {between * 1e3:.1f} ms")


def window_line(out, n: int) -> str:
    """How the loop kept up: admission lateness, calls made, and the
    longest call."""
    import numpy as np
    late = out.admitted - out.due
    q = max(1, n // 4)
    live = [len(p) for *_, p in out.spans.step] or [0]
    return (f"window: {n} requests due in {out.seconds:g} s; loop stopped at"
            f" {out.end:.3f} s; admission late p50"
            f" {np.nanpercentile(late, 50) * 1e3:.3f} ms, p95"
            f" {np.nanpercentile(late, 95) * 1e3:.3f} ms, max"
            f" {np.nanmax(late) * 1e3:.3f} ms, mean first quarter"
            f" {np.nanmean(late[:q]) * 1e3:.3f} ms, last quarter"
            f" {np.nanmean(late[-q:]) * 1e3:.3f} ms; serve calls"
            f" {len(out.spans.serve)}, prefills {len(out.spans.prefill)},"
            f" decode steps {len(out.spans.step)}; live slots mean"
            f" {np.mean(live):.2f}, max {max(live)}; failed"
            f" {int(out.failed.sum())}; {longest_call(out.spans)}")
