"""RG-LRU gated linear recurrence — Pallas TPU kernel.

    h_t = a_t * h_{t-1} + x_t          (a, x: [B, T, W])

The grid is ``(batch, W/block_w, T/chunk)`` with time chunks innermost
(sequential on TPU); the [1, block_w] hidden state persists in VMEM scratch
across chunks. Within a chunk the recurrence is solved by a log-depth
(Hillis-Steele) inclusive scan over (a, x) pairs — combine((a1,x1),(a2,x2))
= (a2*a1, a2*x1 + x2) — vectorised across the width lanes, with the carried
state folded into the first element. Each of its log2(chunk) steps shifts
the tile down the time (sublane) axis with ``pltpu.roll``, which Mosaic
lowers natively; ``lax.associative_scan`` would lower to slices it refuses.

BlockSpec tiling (per grid step, all VMEM):
    a/x  : (1, chunk, block_w)
    init/final state : (1, 1, block_w)   ([B, 1, W], so the last two block
                                          dims are legal for any B)
    state scratch: (1, block_w) f32
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan"]


def _kernel(a_ref, x_ref, s0_ref, h_ref, sf_ref, state, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)                    # [Q, bw]
    x = x_ref[0].astype(jnp.float32)
    t = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    # fold carried state into step 0: x'_0 = a_0 * h_prev + x_0
    x = jnp.where(t == 0, x + a * state[...], x)
    d = 1
    while d < chunk:
        # (a, x)[t] <- (a, x)[t - d] combined with (a, x)[t], for t >= d
        has_prev = t >= d
        x_prev = pltpu.roll(x, d, 0)
        a_prev = pltpu.roll(a, d, 0)
        x = jnp.where(has_prev, a * x_prev + x, x)
        a = jnp.where(has_prev, a * a_prev, a)
        d *= 2
    h_ref[0] = x.astype(h_ref.dtype)
    state[...] = pltpu.roll(x, 1, 0)[:1]                # last step's h

    @pl.when(ci == nc - 1)
    def _final():
        sf_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_scan(a: jnp.ndarray, x: jnp.ndarray,
               init_state: Optional[jnp.ndarray] = None, *,
               chunk: int = 256, block_w: int = 512,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a/x: [B, T, W]. Returns (h [B,T,W] f32, final_state [B,W] f32)."""
    B, T, W = a.shape
    chunk = min(chunk, max(8, T))
    block_w = min(block_w, max(128, W))
    pad_t = (-T) % chunk
    pad_w = (-W) % block_w
    if pad_t or pad_w:
        # a=1, x=0 padding keeps the carried state unchanged
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)),
                    constant_values=1.0)
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_w)))
    Tp, Wp = T + pad_t, W + pad_w
    s0 = (jnp.zeros((B, Wp), jnp.float32) if init_state is None
          else jnp.pad(init_state.astype(jnp.float32), ((0, 0), (0, pad_w))))
    s0 = s0[:, None, :]

    h, sf = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, Wp // block_w, Tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, c: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, block_w), lambda b, w, c: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Wp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, x, s0)
    return h[:, :T, :W], sf[:, 0, :W]
