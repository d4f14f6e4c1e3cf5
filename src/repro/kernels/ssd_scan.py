"""Mamba2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

The sequential recurrence

    s_t = exp(dt_t * A) * s_{t-1} + dt_t * x_t B_t^T
    y_t = C_t s_t (+ D * x_t, added outside the kernel)

is recast per chunk of Q timesteps into MXU-friendly matmuls (the "duality"):
with per-chunk cumulative log-decay cs_t = sum_{r<=t} dt_r*A,

    y_intra = ((C B^T) o L) @ x      L[t,s] = exp(cs_t - cs_s) * dt_s, s <= t
    y_inter = exp(cs)[:,None] * (C @ state^T)
    state'  = exp(cs_Q) * state + (x * (exp(cs_Q - cs)*dt)[:,None])^T @ B

The grid is ``(batch, heads, T/chunk)`` with chunks innermost (sequential on
TPU), so the [hd, N] running state persists in VMEM scratch across chunks.
cs is precomputed outside the kernel (per-chunk cumsum of dt*A), as are the
state-update weights w_t = exp(cs_Q - cs_t) * dt_t and the chunk decay
exp(cs_Q), so the kernel body is pure matmul + elementwise; all exponent
differences are <= 0 for valid (t, s) pairs, so nothing overflows.

Every operand is laid out so that a block's last two dims are either
(8, 128)-aligned or the array's own (Mosaic's tiling rule): x and y are
head-major ``[Bz, H, T, hd]``, and per-timestep scalars come in a row
layout ``[Bz, H, 1, T]`` (time on lanes) and a column layout
``[Bz, H, T, 1]`` (time on sublanes), so no in-kernel transpose is needed.

BlockSpec tiling (per grid step, all VMEM):
    x, y       : (1, 1, Q, hd)      B/C : (1, Q, N)
    dt, cs     : (1, 1, 1, Q)       cs, w : (1, 1, Q, 1)
    exp(cs_Q)  : (1, 1, 1, 1, N)    (one chunk's decay, broadcast over N)
    state scratch: (hd, N) f32
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_chunked"]


def _kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, csc_ref, w_ref, dq_ref,
            s0_ref, y_ref, sf_ref, state, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                # [Q, hd]
    Bm = b_ref[0].astype(jnp.float32)                  # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                  # [Q, N]
    dt = dt_ref[0, 0]                                  # [1, Q] f32
    cs = cs_ref[0, 0]                                  # [1, Q] f32
    cs_t = csc_ref[0, 0]                               # [Q, 1] f32

    # inter-chunk: contribution of the carried state
    y_inter = jnp.exp(cs_t) * jax.lax.dot_general(
        Cm, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [Q, hd]

    # intra-chunk: masked (decay o gram) matmul
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    expo = jnp.where(t_idx >= s_idx, cs_t - cs, -1e30)  # [Q, Q]
    L = jnp.exp(expo) * dt                              # row-bcast dt_s
    y = y_inter + jax.lax.dot_general(
        G * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: exp(cs_Q) * state + (x * w)^T @ B
    state[...] = dq_ref[0, 0, 0] * state[...] + jax.lax.dot_general(
        x * w_ref[0, 0], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # [hd, N]

    @pl.when(ci == nc - 1)
    def _final():
        sf_ref[0, 0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x: jnp.ndarray, B: jnp.ndarray, C: jnp.ndarray,
                dt: jnp.ndarray, A: jnp.ndarray, D: jnp.ndarray,
                init_state: Optional[jnp.ndarray] = None, *,
                chunk: int = 128,
                interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [Bz,T,H,hd]; B/C: [Bz,T,N]; dt: [Bz,T,H]; A/D: [H].

    Returns (y [Bz,T,H,hd] f32, final_state [Bz,H,hd,N] f32).
    """
    Bz, T, H, hd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, max(8, T))
    pad_t = (-T) % chunk
    xp = x
    if pad_t:
        # dt=0 padding preserves the state (exp(0)=1 decay, 0 input weight)
        xp = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad_t), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad_t), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_t), (0, 0)))
    Tp = T + pad_t
    nc = Tp // chunk

    # per-timestep scalars, head-major: [Bz, H, nc, Q]
    dtf = jnp.swapaxes(dt.astype(jnp.float32), 1, 2).reshape(Bz, H, nc, chunk)
    cs = jnp.cumsum(dtf * A[None, :, None, None], axis=-1)
    cq = cs[..., -1:]                                   # chunk-end log-decay
    w = jnp.exp(cq - cs) * dtf
    # chunk decay pre-broadcast over N lanes: Mosaic cannot broadcast a
    # [1, 1] value over sublanes and lanes at once
    dq = jnp.broadcast_to(jnp.exp(cq)[..., None], (Bz, H, nc, 1, N))
    row = lambda a: a.reshape(Bz, H, 1, Tp)             # time on lanes
    col = lambda a: a.reshape(Bz, H, Tp, 1)             # time on sublanes
    x_hm = jnp.swapaxes(xp, 1, 2)                       # [Bz, H, Tp, hd]
    s0 = (jnp.zeros((Bz, H, hd, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    kernel = functools.partial(_kernel, chunk=chunk)
    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c))
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    y, sf = pl.pallas_call(
        kernel,
        grid=(Bz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            row_spec, row_spec, col_spec, col_spec,
            pl.BlockSpec((1, 1, 1, 1, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bz, H, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((Bz, H, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interpret,
    )(x_hm, B, C, row(dtf), row(cs), col(cs), col(w), dq, s0)

    y = jnp.swapaxes(y, 1, 2)[:, :T]
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y, sf
