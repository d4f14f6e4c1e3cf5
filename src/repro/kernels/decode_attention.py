"""Decode attention over a padded KV cache — Pallas TPU kernel.

One new query token per sequence attends to its full cached context. The
grid is ``(batch, S/block_k)`` with the KV dimension innermost (sequential
on TPU); all heads of one sequence are processed together as one batched
``[H, 1, Dp] x [H, Dp, block_k]`` matmul per step.

The wrapper lays K/V out head-major (``[B, H, S, Dp]``) so that each block's
last two dims are ``(block_k, Dp)``, the (8, 128)-aligned tile Mosaic needs.
``lengths`` (valid cache slots per sequence, [B] int32) is a scalar-prefetch
operand: it sits in SMEM before the grid starts, so both the kernel body and
the K/V index maps can read it.

BlockSpec tiling (per grid step, all VMEM):
    q       : (1, H, 1, Dp)
    k/v     : (1, H, block_k, Dp)
    out     : (1, H, 1, Dp)
    scratch : acc (H, 1, Dp) f32, m/l (H, 1, 128) f32 (lane-broadcast)

Blocks entirely beyond ``lengths[b]`` are compute-skipped, and their K/V
index maps clamp to the last valid block so no new tile is fetched for them.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_cost"]


def decode_attention_cost(n_seqs: int, n_heads: int, head_dim: int,
                          ctx: int, *, block_k: int = 256,
                          dtype_bytes: int = 2) -> tuple:
    """Per-layer (flops, hbm_bytes) of one batched decode-attention step,
    derived from THIS kernel's actual tiling — the measured roofline that
    ``StageProfile.decode_step_roofline`` calibrates the analytic
    ``decode_step_time`` against.

    Mirrors the launch math above exactly: the head dim pads to a multiple
    of 128 lanes, the KV axis pads to ``block_k``, and blocks entirely
    beyond ``ctx`` are compute-skipped (``@pl.when``) — so per sequence
    ``ceil(ctx / block_k)`` KV blocks are streamed from HBM and hit the
    MXU. Per touched block each head runs the [H, Dp] x [Dp, bk] logits
    matmul and the [H, bk] x [bk, Dp] update (4 * H * Dp * bk flops); HBM
    traffic is the K and V tiles plus the q read and output write. Pure
    math (no JAX), usable by the control plane.
    """
    S = max(int(ctx), 1)
    bk = min(block_k, max(128, S))
    Dp = head_dim + (-head_dim) % 128
    n_blocks = -(-S // bk)                       # compute-skip beyond ctx
    flops = n_seqs * n_blocks * 4.0 * n_heads * Dp * bk
    hbm = n_seqs * (2.0 * n_blocks * bk * n_heads * Dp * dtype_bytes  # K+V
                    + 2.0 * n_heads * Dp * dtype_bytes)               # q+out
    return flops, hbm

_NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_k: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * block_k < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # [H, 1, Dp]
        k = k_ref[0].astype(jnp.float32)                    # [H, bk, Dp]
        v = v_ref[0].astype(jnp.float32)
        H = q.shape[0]
        # [H, 1, bk] logits: contract Dp, batch over H
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (H, 1, block_k), 2)
        mask = kpos < length
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :, :1]                             # [H, 1, 1]
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # [H, 1, bk]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        # [H, 1, Dp] update: contract bk, batch over H
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[:, :, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, scale: Optional[float] = None,
                     block_k: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: [B,H,D]; k/v: [B,S,H,D]; lengths: [B] int32."""
    B, H, D = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, max(128, S))

    pad_d = (-D) % 128
    pad_s = (-S) % block_k
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_d)))[:, :, None, :]
    k = jnp.pad(jnp.swapaxes(k, 1, 2),
                ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
    v = jnp.pad(jnp.swapaxes(v, 1, 2),
                ((0, 0), (0, 0), (0, pad_s), (0, pad_d)))
    Sp, Dp = S + pad_s, D + pad_d

    def kv_block(b, j, lens):
        # blocks past the valid length re-address the last valid block, so
        # the pipeline skips their fetch (same block index as the step before)
        last = jnp.maximum(lens[b] - 1, 0) // block_k
        return (b, 0, jnp.minimum(j, last), 0)

    kernel = functools.partial(_kernel, scale=scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Sp // block_k),
            in_specs=[
                pl.BlockSpec((1, H, 1, Dp), lambda b, j, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, H, block_k, Dp), kv_block),
                pl.BlockSpec((1, H, block_k, Dp), kv_block),
            ],
            out_specs=pl.BlockSpec((1, H, 1, Dp),
                                   lambda b, j, lens: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1, Dp), jnp.float32),
                pltpu.VMEM((H, 1, 128), jnp.float32),
                pltpu.VMEM((H, 1, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, Dp), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
    return out[:, :, 0, :D]
