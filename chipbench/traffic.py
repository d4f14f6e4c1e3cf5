"""The one traffic generator: every cell's mix is a data file it reads.

A mix (``workloads/<cell>.json``) gives:

* ``rate``: mean requests per second, offered open-loop;
* ``arrival``: ``{"process": "poisson"}`` or ``{"process": "gamma",
  "cv": c}`` (i.i.d. gamma gaps with that coefficient of variation);
* ``contexts``: ``null``, or ``{"lengths": {"1024": 8, ...}, "zipf_s": s,
  "shared": bool}``: a pool of contexts picked per request by Zipf(s) over
  their order; shared contexts are registered in set-up and every prompt
  starts with one, unshared ones only give the prompt its length;
* ``new_tokens``: ``{"128": 0.75, ...}``: the fresh tokens each prompt adds,
  by share of requests;
* ``output``: ``{"median", "sigma", "min", "max"}``: a clipped log-normal
  output length.

Every seed gets the same schedule: the same number of requests, the same
sizes (stratified quantiles and largest-remainder shares of the
distributions above) and the same gaps, in one order fixed for the mix
(``SCHEDULE_SEED``). The seed draws the token ids, and so the shared
contexts. So runs on different seeds differ in content, not in the work or
its timing: a tail over a window's few dozen requests rests on where the
bursts and the long outputs fall, and a fresh order per seed would move it
more than any change to the server.

The arrival gaps follow ``repro.simcluster.trace`` (exponential for
Poisson, gamma with shape ``1/cv**2`` for bursty traffic).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional, Tuple

import numpy as np
from scipy.special import gammaincinv


@dataclass
class Request:
    rid: int
    due: float                  # seconds after the window opens
    tokens: np.ndarray          # int32 prompt
    max_new: int
    context: int = -1           # index into Traffic.contexts, -1: none
    new_len: int = 0            # prompt tokens after the context


@dataclass
class Traffic:
    requests: List[Request]
    contexts: List[np.ndarray]      # shared contexts, registered in set-up
    warmup: List[Request]           # one request per shape the window uses


def _shares(probs: Dict[int, float], n: int) -> List[int]:
    """Largest-remainder allocation of ``n`` items by ``probs``: the
    returned list holds each key as often as its share of ``n``."""
    keys = list(probs)
    p = np.asarray([probs[k] for k in keys], np.float64)
    p = p / p.sum()
    exact = p * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [k for k, c in zip(keys, counts) for _ in range(c)]


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(spec: Dict, n: int) -> np.ndarray:
    """Stratified quantiles of the clipped log-normal output length."""
    z = np.asarray([NormalDist().inv_cdf(u) for u in _quantile_points(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def gaps(arrival: Dict, n: int, span: float) -> np.ndarray:
    """Stratified quantiles of the inter-arrival gap, ``n`` of them
    scaled to sum to ``span``."""
    u = _quantile_points(n)
    if arrival["process"] == "poisson":
        g = -np.log1p(-u)
    elif arrival["process"] == "gamma":
        k = 1.0 / arrival["cv"] ** 2
        g = gammaincinv(k, u)
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    return g * span / g.sum()


def context_lengths(ctx: Dict) -> List[int]:
    """Context pool in Zipf rank order: lengths interleaved, so that every
    length has ranks near the top."""
    by_len = [[int(L)] * c for L, c in ctx["lengths"].items()]
    out = []
    for i in range(max(len(b) for b in by_len)):
        out += [b[i] for b in by_len if i < len(b)]
    return out


def _zipf(n_ctx: int, s: float) -> Dict[int, float]:
    w = np.arange(1, n_ctx + 1, dtype=np.float64) ** -s
    return {i: float(x) for i, x in enumerate(w / w.sum())}


def shapes(mix: Dict) -> List[Tuple[int, int]]:
    """Every (context length, new tokens) pair the mix can draw; context
    length 0 for a mix without contexts."""
    news = sorted(int(k) for k in mix["new_tokens"])
    if mix.get("contexts"):
        lens = sorted(set(context_lengths(mix["contexts"])))
        return [(L, b) for L in lens for b in news]
    return [(0, b) for b in news]


def max_tokens(mix: Dict) -> int:
    """Longest prompt plus longest output of the mix."""
    return max(L + b for L, b in shapes(mix)) + int(mix["output"]["max"])


#: the one draw of every mix's order of arrivals, shapes and outputs
SCHEDULE_SEED = 20260417


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """The requests due in a window of ``seconds``, their shared contexts
    and one warm-up request per shape; token ids from ``seed``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(SCHEDULE_SEED)
    rate = float(mix["rate"])
    n = max(1, int(round(rate * seconds)))
    tok = lambda m: rng.integers(0, vocab, m, dtype=np.int32)

    ctx = mix.get("contexts")
    shared = bool(ctx and ctx.get("shared"))
    pool = context_lengths(ctx) if ctx else []
    contexts = [tok(L) for L in pool] if shared else []

    # one joint draw of (context, new tokens), so every seed gets the same
    # multiset of prompt shapes
    pc = _zipf(len(pool), ctx["zipf_s"]) if ctx else {-1: 1.0}
    pb = {int(k): v for k, v in mix["new_tokens"].items()}
    pairs = _shares({(c, b): pc[c] * pb[b] for c in pc for b in pb}, n)
    pairs = [pairs[i] for i in order.permutation(n)]
    outs = order.permutation(output_lengths(mix["output"], n))
    # n gaps, the first before the first request; the last request falls
    # half a mean gap before the window's end
    due = np.cumsum(order.permutation(
        gaps(mix["arrival"], n, seconds * n / (n + 0.5))))

    reqs = []
    for i, (c, b) in enumerate(pairs):
        if shared:
            prompt = np.concatenate([contexts[c], tok(b)])
        else:
            prompt = tok((pool[c] if c >= 0 else 0) + b)
        reqs.append(Request(rid=i, due=float(due[i]), tokens=prompt,
                            max_new=int(outs[i]),
                            context=c if shared else -1, new_len=b))

    warm = []
    for L, b in shapes(mix):
        if shared:
            c = pool.index(L)
            prompt = np.concatenate([contexts[c], tok(b)])
        else:
            prompt = tok(L + b)
        warm.append(Request(rid=-1 - len(warm), due=0.0, tokens=prompt,
                            max_new=2, context=c if shared else -1,
                            new_len=b))
    return Traffic(requests=reqs, contexts=contexts, warmup=warm)


def scaled(mix: Dict, length_div: int, output_div: int) -> Dict:
    """A copy of ``mix`` with every length cut by a factor (CPU rehearsal
    and tests at a small size): the same buckets, shares and arrivals."""
    out = dict(mix)
    if mix.get("contexts"):
        out["contexts"] = dict(mix["contexts"], lengths={
            str(int(L) // length_div): c
            for L, c in mix["contexts"]["lengths"].items()})
    out["new_tokens"] = {str(max(1, int(k) // length_div)): v
                         for k, v in mix["new_tokens"].items()}
    o = mix["output"]
    out["output"] = {"median": max(2, o["median"] // output_div),
                     "sigma": o["sigma"],
                     "min": max(2, o["min"] // output_div),
                     "max": max(2, o["max"] // output_div)}
    return out
