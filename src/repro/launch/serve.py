"""Serving launcher: disaggregated P/D cluster with MFS-scheduled transfers.

Runs the real JAX engine (the reduced SMOKE config by default; the published
widths with ``--full``) under the DisaggServer orchestrator and reports
per-request TTFT / SLO attainment per scheduling policy. TTFT is read off
the virtual clock, priced by the peak table of the chip the process runs on
(off a chip: TPU v5e's).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --requests 16 --rps 200 --policy mfs [--policy fs ...] [--full]
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import numpy as np

from ..configs import ARCHS, SMOKES
from ..core import make_policy
from ..models.lm import build_model
from ..serving import DisaggConfig, DisaggServer, ServeRequest
from ..simcluster.hw import HW, TPU_V5E
from .cache import enable_compile_cache

__all__ = ["make_requests", "run"]


def make_requests(cfg, n: int, rps: float, seed: int = 0,
                  reuse_rate: float = 0.5, mean_prompt: int = 48,
                  max_new: int = 4):
    """Synthetic request stream with Zipf-hot shared prefixes (the paper's
    agent-workload shape at toy scale)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab, size=(32,)) for _ in range(4)]
    pmf = np.array([1.0 / (i + 1) ** 1.6 for i in range(4)])
    pmf /= pmf.sum()
    gaps = rng.exponential(1.0 / rps, size=n)
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(n):
        ln = int(np.clip(rng.lognormal(np.log(mean_prompt), 0.4), 16, 512))
        if rng.uniform() < reuse_rate:
            pfx = prefixes[rng.choice(4, p=pmf)]
            toks = np.concatenate([pfx, rng.integers(0, cfg.vocab,
                                                     size=(max(1, ln - 32),))])
        else:
            toks = rng.integers(0, cfg.vocab, size=(ln,))
        out.append(ServeRequest(rid=i, arrival=float(arrivals[i]),
                                tokens=toks, max_new=max_new))
    return out


def run(arch: str, *, smoke: bool = True, n_requests: int = 16,
        rps: float = 200.0, policies=("mfs",), seed: int = 0,
        n_units: int = 2, verbose: bool = True, hw: Optional[HW] = None):
    cfg = (SMOKES if smoke else ARCHS)[arch]
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    reqs = make_requests(cfg, n_requests, rps, seed)
    summary = {}
    for pol in policies:
        srv = DisaggServer(model, params, policy=make_policy(pol),
                           cfg=DisaggConfig(n_prefill_units=n_units, hw=hw))
        res = srv.serve(reqs)
        slo = sum(r.met_slo for r in res) / len(res)
        mean_ttft = float(np.mean([r.ttft for r in res]))
        reuse = sum(r.reused_tokens for r in res) / max(
            1, sum(len(r0.tokens) for r0 in reqs))
        summary[pol] = {"slo_attainment": slo, "mean_ttft_ms": mean_ttft * 1e3,
                        "reuse_fraction": reuse}
        if verbose:
            print(f"{pol:10s} slo={slo:6.3f} mean_ttft={mean_ttft * 1e3:8.3f}ms"
                  f" reuse={reuse:.2%}", flush=True)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the published widths instead of the SMOKE preset")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rps", type=float, default=200.0)
    ap.add_argument("--policy", action="append", default=None)
    ap.add_argument("--units", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    enable_compile_cache()
    on_chip = jax.default_backend() == "tpu"
    run(a.arch, smoke=a.smoke, n_requests=a.requests, rps=a.rps,
        policies=tuple(a.policy or ["mfs", "fs", "sjf", "edf", "karuna"]),
        seed=a.seed, n_units=a.units, hw=None if on_chip else TPU_V5E)


if __name__ == "__main__":
    main()
