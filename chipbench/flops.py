"""Operations and bytes the model needs, from its shapes.

Counts use the model's own query heads (not the count padded for
sharding) and a causal mask, and leave out decode lanes that hold no
request. ``m`` is ``model.dims`` of a configuration. A matmul of
``[n, a] x [a, b]`` counts ``2 n a b``. The weight matmuls of a token are
the family's own count (``families/<model_type>.py``); attention and the
logits are counted here from the shapes every family gives.
"""
from __future__ import annotations

from typing import Dict, Tuple

from . import model

BYTES = 2                       # bfloat16 weights, activations and cache


def matmul_per_token(m: Dict) -> float:
    """Weight matmuls of all layers for one token (no logits)."""
    return model.family(m).matmul_per_token(m)


def attention_pairs(q_len: int, prefix: int) -> float:
    """(query, key) pairs under a causal mask: ``q_len`` queries after
    ``prefix`` cached tokens."""
    return q_len * prefix + q_len * (q_len + 1) / 2.0


def attention(m: Dict, q_len: int, prefix: int) -> float:
    """Scores and weighted values of one layer (``QK^T`` and ``PV``)."""
    return 4.0 * m["head_dim"] * m["n_heads"] * attention_pairs(q_len,
                                                                 prefix)


def logits(m: Dict, rows: int = 1) -> float:
    return 2.0 * m["d_model"] * m["vocab"] * rows


def prefill(m: Dict, q_len: int, prefix: int) -> float:
    """One prefill of ``q_len`` tokens over ``prefix`` reused ones; the
    served model computes logits of the last position only."""
    return (q_len * matmul_per_token(m)
            + m["n_layers"] * attention(m, q_len, prefix) + logits(m))


def decode(m: Dict, pos: int) -> float:
    """One decoded token written at position ``pos``."""
    return (matmul_per_token(m) + m["n_layers"] * attention(m, 1, pos)
            + logits(m))


def flash_call(m: Dict, q_len: int, prefix: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's prefill attention kernel: read Q of
    the model's heads and K, V of its KV heads, write the output."""
    h, kv, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    s = prefix + q_len
    nbytes = BYTES * hd * (2 * q_len * h + 2 * s * kv)
    return attention(m, q_len, prefix), float(nbytes)
