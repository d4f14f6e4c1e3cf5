"""What decides ``correct``: served greedy tokens against the reference.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is run
through the float32 reference of the configuration's family
(``families/<model_type>.py``) over its prompt and served tokens. At each
served position the reading is how far the served token's reference logit
lies below the reference's best logit there; the number compared is the
widest such gap over the sample. The first served token comes from the
prefill that the request took in the window (full, or suffix over pages
gathered from the prefix index), the others from ``DecodeBatch`` steps
through the stacked slot cache.

The control reads, at the same positions, the gap of the token that the
float8 reference puts first. Both readings go through ``decide`` against the
workload's ``check.max_logit_gap``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import model


@dataclass
class Sample:
    rids: List[int]
    seqs: List[np.ndarray]      # prompt + served tokens but the last
    rows: List[np.ndarray]      # positions that predict the served tokens
    served: List[np.ndarray]    # the served tokens


def draw(finished: Dict[int, tuple], seed: int, budget_tokens: int,
         max_seqs: int) -> Sample:
    """``finished``: rid -> (prompt, served tokens). The request with the
    most served tokens, then others in an order drawn from the seed, until
    ``budget_tokens`` served tokens or ``max_seqs`` sequences."""
    out = Sample([], [], [], [])
    if not finished:
        return out
    rng = np.random.default_rng([seed, 1])
    longest = max(finished, key=lambda r: (len(finished[r][1]), -r))
    order = [longest] + [int(r) for r in rng.permutation(sorted(finished))
                         if r != longest]
    n_tok = 0
    for rid in order:
        if n_tok >= budget_tokens or len(out.rids) >= max_seqs:
            break
        prompt, toks = finished[rid]
        toks = np.asarray(toks, np.int32)
        out.rids.append(rid)
        out.seqs.append(np.concatenate([prompt, toks[:-1]]).astype(np.int32))
        out.rows.append(np.arange(len(prompt) - 1,
                                  len(prompt) - 1 + len(toks)))
        out.served.append(toks)
        n_tok += len(toks)
    return out


def gaps(ref_logits: Sequence[np.ndarray],
         chosen: Sequence[np.ndarray]) -> np.ndarray:
    """Per position: best reference logit minus the chosen token's."""
    g = [lg.max(-1) - np.take_along_axis(lg, c[:, None], -1)[:, 0]
         for lg, c in zip(ref_logits, chosen)]
    return np.concatenate(g) if g else np.zeros(0)


def limit(mix: Dict) -> float:
    """The workload's limit on the widest gap."""
    return float(mix["check"]["max_logit_gap"])


def decide(gap: Optional[float], max_gap: float) -> bool:
    """``correct``: a gap was read (some request finished) and it is within
    the limit."""
    return gap is not None and gap <= max_gap


def served_gap(dims: Dict, seed: int, s: Sample) -> float:
    """Widest gap of a served token below the reference's best."""
    ref = model.family(dims).logits(dims, seed, s.seqs, s.rows)
    return float(gaps(ref, s.served).max())


def control_gap(dims: Dict, seed: int, s: Sample) -> float:
    """Widest gap of the float8 reference's first choice."""
    logits = model.family(dims).logits
    ref = logits(dims, seed, s.seqs, s.rows)
    ctl = logits(dims, seed, s.seqs, s.rows, fp8=True)
    return float(gaps(ref, [c.argmax(-1) for c in ctl]).max())
