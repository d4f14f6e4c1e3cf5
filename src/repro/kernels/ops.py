"""Dispatching wrappers: Pallas TPU kernels when available, jnp oracles
otherwise.

Selection order:
  1. a TPU backend (or ``REPRO_USE_PALLAS=1``) -> compiled pallas_call
     kernels with BlockSpec VMEM tiling;
  2. ``REPRO_PALLAS_INTERPRET=1`` -> same kernels, interpret mode (CPU CI);
  3. otherwise -> the pure-jnp reference (ref.py), which XLA fuses well and
     which the dry-run lowers through.

On a TPU backend the compiled kernels are the only choice: asking there for
the reference (``REPRO_USE_PALLAS=0``) or for interpret mode raises, so a
chip run never silently measures something else.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref

__all__ = ["attention", "decode_attention", "ssd", "rglru", "use_pallas",
           "interpret_mode"]


def use_pallas() -> bool:
    """Compiled Pallas kernels: always on a TPU backend, elsewhere only with
    ``REPRO_USE_PALLAS=1``."""
    flag = os.environ.get("REPRO_USE_PALLAS")
    if jax.default_backend() == "tpu":
        if flag == "0":
            raise RuntimeError("REPRO_USE_PALLAS=0 on a TPU backend: the "
                               "chip path always runs the Pallas kernels")
        return True
    return flag == "1"


def interpret_mode() -> bool:
    if os.environ.get("REPRO_PALLAS_INTERPRET") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError("REPRO_PALLAS_INTERPRET=1 on a TPU backend: the "
                           "chip path runs the compiled kernels")
    return True


def _pallas_enabled() -> bool:
    return use_pallas() or interpret_mode()


#: above this many score elements per head the XLA path switches to the
#: custom-VJP flash implementation (O(block^2) live scores in fwd AND bwd)
_FLASH_THRESHOLD = 2048 * 2048
if os.environ.get("REPRO_BASELINE_FULL_ATTN") == "1":   # §Perf kill-switch
    _FLASH_THRESHOLD = 1 << 62


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset: int = 0, scale: Optional[float] = None):
    """Multi-head attention, q:[B,T,H,D] k/v:[B,S,H,D] (heads already
    aligned — GQA resolution happens in the model layer)."""
    if _pallas_enabled():
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, scale=scale,
                               interpret=interpret_mode())
    T, S = q.shape[1], k.shape[1]
    if T * S > _FLASH_THRESHOLD:
        import math
        from .flash_xla import flash_attention_xla
        s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        return flash_attention_xla(q, k, v, s, causal, window, q_offset)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset, scale=scale)


def decode_attention(q, k, v, lengths, *, scale: Optional[float] = None):
    if _pallas_enabled():
        from .decode_attention import decode_attention as da
        return da(q, k, v, lengths, scale=scale, interpret=interpret_mode())
    return _ref.decode_attention_ref(q, k, v, lengths, scale=scale)


def ssd(x, B, C, dt, A, D, init_state=None,
        ref_fallback: Optional[Callable] = None):
    """Mamba2 SSD. Returns (y, final_state)."""
    if _pallas_enabled():
        from .ssd_scan import ssd_chunked
        return ssd_chunked(x, B, C, dt, A, D, init_state=init_state,
                           interpret=interpret_mode())
    if x.shape[1] > 16 and os.environ.get("REPRO_BASELINE_SSD_SCAN") != "1":
        # chunked dual form: O(T/Q) differentiation memory (§Perf iter. 3)
        return _ref.ssd_dual(x, B, C, dt, A, D, init_state=init_state)
    return _ref.ssd_ref(x, B, C, dt, A, D, init_state=init_state)


def rglru(a, x, init_state=None):
    """Gated linear recurrence. Returns (h, final_state)."""
    if _pallas_enabled():
        from .rglru import rglru_scan
        return rglru_scan(a, x, init_state=init_state,
                          interpret=interpret_mode())
    return _ref.rglru_ref(a, x, init_state=init_state)
