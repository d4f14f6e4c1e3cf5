"""Model blocks: attention (GQA / local / MLA), FFN (dense / MoE-EP),
Mamba2 SSD mixer, RG-LRU mixer.

Every block is a (init, apply) pair of pure functions. ``apply`` supports
three modes:
  * train    — full-sequence causal, no cache
  * prefill  — full-sequence causal, returns a decode cache
  * decode   — single-token step against a fixed-capacity cache

MoE uses an expert-parallel shard_map with explicit dispatch/combine
``all_to_all`` collectives over the "model" mesh axis — the Stage-2 traffic
MFS schedules, and the collective the roofline analysis counts. On a single
device (CPU tests) the same math runs through the local path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from .layers import (DEFAULT_DTYPE, apply_rope, dense, gqa_attention,
                     init_dense, rmsnorm, rmsnorm_params, rope, swiglu,
                     swiglu_params)
from .sharding import ShardCtx, pad_to_multiple

__all__ = [
    "AttnDims", "attn_init", "attn_apply",
    "mla_init", "mla_apply",
    "ffn_init", "ffn_apply",
    "moe_init", "moe_apply",
    "ssd_init", "ssd_apply",
    "rglru_init", "rglru_apply",
]


# =====================================================================
# KV-cache quantisation (int8 storage for HBM-bound decode cells)
# =====================================================================
_KV_QSCALE = 32.0          # static symmetric scale; clip range ~ +/-4


def _kv_store(x: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * _KV_QSCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(dtype)


def _kv_load(x: jnp.ndarray, dtype) -> jnp.ndarray:
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) / _KV_QSCALE).astype(dtype)
    return x


# =====================================================================
# GQA attention (with optional sliding window + QKV bias)
# =====================================================================
@dataclass(frozen=True)
class AttnDims:
    """Padded head layout for TP divisibility (see models/sharding.py).

    Query heads are padded to a multiple of the model axis and sharded; the
    padded heads are exact no-ops (zero W_o columns). KV heads:
      * MHA (n_kv == n_heads): padded alongside and sharded identically;
      * GQA: kept at their true count and replicated across the model axis —
        at compute time a static gather maps each (padded) query head to its
        KV head, and the gathered tensor is sharding-constrained so each
        device materialises only its own q-heads' copies.
    This keeps every assigned architecture (15, 24, 28, 32, 40 heads; 1-40 KV
    heads) shardable on a 16-wide model axis without semantic change.
    """

    n_q: int           # padded query heads
    n_kv: int          # stored kv heads (== n_q when MHA-sharded)
    kv_sharded: bool
    hd: int

    @staticmethod
    def of(cfg: ArchConfig, ctx: ShardCtx) -> "AttnDims":
        m = ctx.head_multiple          # mesh-independent layout (ckpt-stable)
        n_q = pad_to_multiple(cfg.n_heads, m)
        if cfg.n_kv == cfg.n_heads:                 # MHA: pad both, shard kv
            return AttnDims(n_q, n_q, True, cfg.hd)
        return AttnDims(n_q, cfg.n_kv, False, cfg.hd)

    def q_to_kv(self, cfg: ArchConfig) -> jnp.ndarray:
        """Static map: padded query head -> kv head index."""
        rep = max(1, cfg.n_heads // cfg.n_kv)
        idx = [min(h // rep, self.n_kv - 1) for h in range(self.n_q)]
        return jnp.asarray(idx, jnp.int32)


def _grouped_ok(cfg: ArchConfig, dims: AttnDims, n_store: int) -> bool:
    """True when the static q->kv map is the uniform grouping h -> h//rep,
    so grouped attention can consume the raw (unexpanded) KV heads. Holds
    for MQA (all heads -> kv 0) and whenever no padded q heads exist."""
    import os
    if os.environ.get("REPRO_BASELINE_EXPAND_KV") == "1":
        return False                      # §Perf baseline kill-switch
    if n_store <= 0 or dims.n_q % n_store != 0:
        return False
    rep = dims.n_q // n_store
    real_rep = max(1, cfg.n_heads // max(1, cfg.n_kv))
    return all(min(h // real_rep, n_store - 1) == h // rep
               for h in range(dims.n_q))


def _per_head(ctx: ShardCtx, fn, *xs):
    """Run a [B, T, H, D] attention kernel on each device's own heads (and
    batch rows). Mosaic kernels cannot be partitioned by the compiler, so
    over a mesh the call goes through ``shard_map``; heads are independent,
    so the result is the same as one call over all of them."""
    if ctx.mesh is None:
        return fn(*xs)
    batch = ctx.pspec("batch")[0] if xs[0].shape[0] % ctx.data_size == 0 \
        else None
    spec = P(batch, None, ctx.model_axis, None)
    # check_vma=False: pallas_call declares no per-axis variance
    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=(spec,) * len(xs),
                         out_specs=spec, check_vma=False)(*xs)


def attn_init(key, cfg: ArchConfig, ctx: ShardCtx, dtype=DEFAULT_DTYPE):
    dims = AttnDims.of(cfg, ctx)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": init_dense(kq, d, dims.n_q * dims.hd, dtype, bias=cfg.qkv_bias),
        "wk": init_dense(kk, d, dims.n_kv * dims.hd, dtype, bias=cfg.qkv_bias),
        "wv": init_dense(kv, d, dims.n_kv * dims.hd, dtype, bias=cfg.qkv_bias),
        "wo": init_dense(ko, dims.n_q * dims.hd, d, dtype),
    }
    # zero the padded query heads' output columns => exact no-op heads
    real = cfg.n_heads * dims.hd
    if dims.n_q * dims.hd > real:
        p["wo"]["w"] = p["wo"]["w"].at[real:, :].set(0.0)
    return p


def _kv_cache_shape(cfg: ArchConfig, ctx: ShardCtx, batch: int, max_len: int,
                    dtype) -> Dict[str, Any]:
    dims = AttnDims.of(cfg, ctx)
    S = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "k": jnp.zeros((batch, S, dims.n_kv, dims.hd), dtype),
        "v": jnp.zeros((batch, S, dims.n_kv, dims.hd), dtype),
    }


def attn_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx, mode: str,
               cache: Optional[Dict] = None, pos: int | jax.Array = 0,
               window: int = 0):
    """x: [B, T, D]. Returns (y, new_cache)."""
    B, T, D = x.shape
    dims = AttnDims.of(cfg, ctx)
    q = dense(p["wq"], x).reshape(B, T, dims.n_q, dims.hd)
    k = dense(p["wk"], x).reshape(B, T, dims.n_kv, dims.hd)
    v = dense(p["wv"], x).reshape(B, T, dims.n_kv, dims.hd)
    positions = pos + jnp.arange(T)[None, :]                       # [1, T]
    sin, cos = rope(positions, dims.hd, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    q = ctx.act(q, ("batch", None, "model", None))
    seq_shard = ctx.kv_seq_shard and mode == "decode"
    kv_spec = (("batch", "model", None, None) if seq_shard
               else ("batch", None, "model" if dims.kv_sharded else None, None))

    new_cache = None
    if mode == "decode":
        assert cache is not None and T == 1
        S = cache["k"].shape[1]
        kv_dtype = cache["k"].dtype
        n_store = cache["k"].shape[2]
        if n_store != dims.n_kv:
            # cache stores the REAL kv heads only (padded MHA heads are
            # no-ops); crop before insert, expand via q_to_kv after load
            k = k[:, :, :n_store]
            v = v[:, :, :n_store]
        if window:
            slot = jnp.asarray(pos) % S
        else:
            slot = jnp.asarray(pos)
        ck = jax.lax.dynamic_update_slice(cache["k"], _kv_store(k, kv_dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], _kv_store(v, kv_dtype),
                                          (0, slot, 0, 0))
        if ctx.mesh is not None:
            ck = ctx.act(ck, kv_spec)
            cv = ctx.act(cv, kv_spec)
        new_cache = {"k": ck, "v": cv}
        k_all, v_all = _kv_load(ck, k.dtype), _kv_load(cv, v.dtype)
        k_pos = jnp.arange(S)
        if window:
            # rolling buffer: entry i holds absolute position with i == pos%S
            age = (slot - k_pos) % S
            abs_pos = jnp.asarray(pos) - age
            valid = (abs_pos >= 0) & (age < jnp.minimum(window, jnp.asarray(pos) + 1))
            mask = valid[None, None, :]
        else:
            mask = (k_pos[None, None, :] <= jnp.asarray(pos))
        # rope for cached keys was applied at insert time
    elif cache is not None and mode == "prefill":
        # suffix prefill over a reused prefix cache (Stage-1 KV reuse): the
        # prefix holds absolute positions [pos - Pk, pos); queries start at
        # pos, so the attention kernel sees q_offset = Pk (positions are
        # contiguous and masks depend only on position differences).
        Pk = cache["k"].shape[1]
        k_all = jnp.concatenate([cache["k"], k], axis=1)
        v_all = jnp.concatenate([cache["v"], v], axis=1)
        q_offset = Pk
        mask = None                                # kernel builds the mask
        new_cache = {"k": k_all, "v": v_all}
        if window:
            W = min(window, Pk + T)
            new_cache = {"k": k_all[:, -W:], "v": v_all[:, -W:]}
    else:
        k_all, v_all = k, v
        if mode == "encode":                       # bidirectional
            mask = jnp.ones((1, T, T), bool)
        else:
            qp = positions[0][:, None]
            kp = positions[0][None, :]
            m2 = qp >= kp
            if window:
                m2 &= (qp - kp) < window
            mask = m2[None]
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
            if window:
                W = min(window, T)
                new_cache = {"k": k[:, T - W:], "v": v[:, T - W:]}
    k_all = ctx.act(k_all, kv_spec)
    v_all = ctx.act(v_all, kv_spec)
    grouped = (mode == "decode"
               and _grouped_ok(cfg, dims, k_all.shape[2]))
    if k_all.shape[2] != dims.n_q and not grouped:
        # non-uniform q->kv map (padded q heads straddle groups): expand KV
        # to the padded head count. Uniform cases skip this — the grouped
        # attention path reads each KV head once instead of rep times
        # (§Perf iteration 1: HBM term of decode cells).
        qmap = jnp.minimum(dims.q_to_kv(cfg), k_all.shape[2] - 1)
        k_all = jnp.take(k_all, qmap, axis=2)      # static gather -> [B,S,nq,hd]
        v_all = jnp.take(v_all, qmap, axis=2)
        post_spec = (("batch", "model", None, None) if seq_shard
                     else ("batch", None, "model", None))
        k_all = ctx.act(k_all, post_spec)
        v_all = ctx.act(v_all, post_spec)
    from ..kernels import ops as kops
    if mode == "decode":
        out = gqa_attention(q, k_all, v_all, mask=mask)
    else:
        q_off = q_offset if (cache is not None and mode == "prefill") else 0
        out = _per_head(ctx, partial(kops.attention, causal=(mode != "encode"),
                                     window=window, q_offset=q_off),
                        q, k_all, v_all)
    out = ctx.act(out, ("batch", None, "model", None))
    y = dense(p["wo"], out.reshape(B, T, dims.n_q * dims.hd))
    return ctx.act(y, ("batch", None, None)), new_cache


# =====================================================================
# MLA — Multi-head Latent Attention (DeepSeek-V3)
# =====================================================================
def mla_init(key, cfg: ArchConfig, ctx: ShardCtx, dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 6)
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, r, qr = (cfg.nope_head_dim, cfg.rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank, cfg.q_lora_rank)
    return {
        "wq_a": init_dense(ks[0], d, qr, dtype),
        "q_norm": rmsnorm_params(qr),
        "wq_b": init_dense(ks[1], qr, H * (dn + dr), dtype),
        "wkv_a": init_dense(ks[2], d, r + dr, dtype),
        "kv_norm": rmsnorm_params(r),
        "wk_b": init_dense(ks[3], r, H * dn, dtype),
        "wv_b": init_dense(ks[4], r, H * dv, dtype),
        "wo": init_dense(ks[5], H * dv, d, dtype),
    }


def mla_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx, mode: str,
              cache: Optional[Dict] = None, pos: int | jax.Array = 0,
              window: int = 0):
    B, T, D = x.shape
    H = cfg.n_heads
    dn, dr, dv, r = (cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    q = dense(p["wq_b"], rmsnorm(p["q_norm"], dense(p["wq_a"], x)))
    q = q.reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = dense(p["wkv_a"], x)                       # [B, T, r + dr]
    c_kv = rmsnorm(p["kv_norm"], kv[..., :r])       # latent (this IS the cache)
    k_rope = kv[..., r:]                            # shared rope key, 1 "head"
    positions = pos + jnp.arange(T)[None, :]
    sin, cos = rope(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0, :]

    new_cache = None
    if mode == "decode":
        assert cache is not None and T == 1
        slot = jnp.asarray(pos)
        cc = jax.lax.dynamic_update_slice(cache["c"], c_kv, (0, slot, 0))
        cr = jax.lax.dynamic_update_slice(cache["kr"], k_rope, (0, slot, 0))
        if ctx.mesh is not None and ctx.kv_seq_shard:
            # flash-decoding layout: latent cache sequence-sharded over the
            # model axis; XLA assembles the softmax across shards
            cc = ctx.act(cc, ("batch", "model", None))
            cr = ctx.act(cr, ("batch", "model", None))
        new_cache = {"c": cc, "kr": cr}
        c_all, kr_all = cc, cr
        S = cc.shape[1]
        mask = (jnp.arange(S)[None, None, :] <= slot)
    elif cache is not None and mode == "prefill":
        # suffix prefill over a reused latent prefix (Stage-1 KV reuse)
        Pk = cache["c"].shape[1]
        c_all = jnp.concatenate([cache["c"], c_kv], axis=1)
        kr_all = jnp.concatenate([cache["kr"], k_rope], axis=1)
        qp = positions[0][:, None]
        kp = (jnp.asarray(pos) - Pk + jnp.arange(Pk + T))[None, :]
        mask = (qp >= kp)[None]
        new_cache = {"c": c_all, "kr": kr_all}
    else:
        c_all, kr_all = c_kv, k_rope
        m2 = causal = (positions[0][:, None] >= positions[0][None, :])
        mask = m2[None]
        if mode == "prefill":
            new_cache = {"c": c_kv, "kr": k_rope}

    # absorbed attention: score = q_nope · (W_kb^T c) + q_rope · k_rope
    wk = p["wk_b"]["w"].reshape(r, H, dn)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))      # [B,T,H,r]
    q_lat = ctx.act(q_lat, ("batch", None, "model", None))
    scale = 1.0 / math.sqrt(dn + dr)
    s1 = jnp.einsum("bthr,bsr->bhts", q_lat, c_all.astype(jnp.float32))
    s2 = jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                    kr_all.astype(jnp.float32))
    logits = (s1 + s2) * scale
    logits = jnp.where(mask[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    ctx_lat = jnp.einsum("bhts,bsr->bthr", w, c_all.astype(jnp.float32))
    wv = p["wv_b"]["w"].reshape(r, H, dv)
    out = jnp.einsum("bthr,rhv->bthv", ctx_lat, wv.astype(jnp.float32))
    out = ctx.act(out.astype(x.dtype), ("batch", None, "model", None))
    y = dense(p["wo"], out.reshape(B, T, H * dv))
    return ctx.act(y, ("batch", None, None)), new_cache


# =====================================================================
# Dense FFN
# =====================================================================
def ffn_init(key, cfg: ArchConfig, ctx: ShardCtx, d_ff: Optional[int] = None,
             dtype=DEFAULT_DTYPE):
    return swiglu_params(key, cfg.d_model, d_ff or cfg.d_ff, dtype)


def ffn_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx):
    h = jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x)
    h = ctx.act(h, ("batch", None, "model"))
    y = dense(p["wo"], h)
    return ctx.act(y, ("batch", None, None))


# =====================================================================
# MoE FFN — expert parallel over the "model" axis with explicit all_to_all
# =====================================================================
def moe_init(key, cfg: ArchConfig, ctx: ShardCtx, dtype=DEFAULT_DTYPE):
    ks = jax.random.split(key, 5)
    d, E, F = cfg.d_model, cfg.n_experts, cfg.d_expert or cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale),
        "w_in": (jax.random.normal(ks[1], (E, d, F), jnp.float32) * scale).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (E, d, F), jnp.float32) * scale).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (E, F, d), jnp.float32)
                  * (1.0 / math.sqrt(F))).astype(dtype),
    }
    if cfg.n_shared:
        p["shared"] = swiglu_params(ks[4], d, cfg.n_shared * F, dtype)
    return p


def _expert_ffn(w_in, w_gate, w_out, x, group_sizes):
    """Grouped SwiGLU over tokens sorted by expert (ragged_dot)."""
    h = jax.nn.silu(jax.lax.ragged_dot(x, w_gate, group_sizes)) * \
        jax.lax.ragged_dot(x, w_in, group_sizes)
    return jax.lax.ragged_dot(h, w_out, group_sizes)


def _route(x_flat, router, top_k):
    probs = jax.nn.softmax(x_flat.astype(jnp.float32) @ router, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)                # [N, K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _moe_token_gather(p, x, cfg: ArchConfig):
    """Per-token expert GEMV via weight gather — the decode path (few
    tokens, top-k experts each). vmap-friendly (no ragged_dot), which the
    slotted decode engine relies on."""
    B, T, D = x.shape
    xf = x.reshape(-1, D)
    gates, idx = _route(xf, p["router"], cfg.top_k)          # [N,K]
    w_in = p["w_in"][idx]                                    # [N,K,D,F]
    w_g = p["w_gate"][idx]
    w_o = p["w_out"][idx]                                    # [N,K,F,D]
    h = jax.nn.silu(jnp.einsum("nd,nkdf->nkf", xf, w_g)) * \
        jnp.einsum("nd,nkdf->nkf", xf, w_in)
    y = jnp.einsum("nkf,nkfd->nd", h * gates[..., None].astype(h.dtype), w_o)
    return y.reshape(B, T, D).astype(x.dtype)


def _moe_local(p, x, cfg: ArchConfig):
    """Single-device MoE: sort-by-expert + ragged grouped matmuls."""
    B, T, D = x.shape
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    K, E = cfg.top_k, cfg.n_experts
    gates, idx = _route(xf, p["router"], K)
    flat_e = idx.reshape(-1)                                # [N*K]
    order = jnp.argsort(flat_e)
    toks = xf[order // K]
    gs = jnp.bincount(flat_e, length=E)
    y = _expert_ffn(p["w_in"], p["w_gate"], p["w_out"], toks, gs)
    y = y * gates.reshape(-1)[order][:, None].astype(y.dtype)
    out = jnp.zeros_like(xf).at[order // K].add(y)
    return out.reshape(B, T, D)   # shared experts are added by moe_apply


def _axis_size(axis) -> int:
    if isinstance(axis, str):
        return jax.lax.axis_size(axis)
    n = 1
    for a in axis:
        n *= jax.lax.axis_size(a)
    return n


def _axis_index(axis):
    """Row-major linearised index over a (possibly tuple) axis name."""
    if isinstance(axis, str):
        return jax.lax.axis_index(axis)
    idx = 0
    for a in axis:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _moe_ep_body(xf, router, w_in, w_gate, w_out, *, cfg: ArchConfig,
                 axis, capacity_factor: float):
    """Per-shard EP body. xf: [N_loc, D] local tokens; expert weights local
    [E_loc, ...]. Dispatch/combine are explicit all_to_all over ``axis`` —
    the paper's Stage-2 collectives."""
    ep = _axis_size(axis)
    E_loc = w_in.shape[0]
    N, D = xf.shape
    K = cfg.top_k
    gates, idx = _route(xf, router, K)                      # global expert ids
    dest = idx // E_loc                                     # [N, K] shard id
    e_loc = idx % E_loc
    cap = max(1, int(math.ceil(N * K / ep * capacity_factor)))
    # position of each (token, k) within its destination buffer
    d_flat = dest.reshape(-1)
    onehot = jax.nn.one_hot(d_flat, ep, dtype=jnp.int32)    # [N*K, ep]
    pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(N * K), d_flat]
    valid = pos < cap
    tok_src = jnp.arange(N * K) // K
    safe_d = jnp.where(valid, d_flat, 0)
    safe_p = jnp.where(valid, pos, 0)
    send_x = jnp.zeros((ep, cap, D), xf.dtype)
    send_x = send_x.at[safe_d, safe_p].set(
        jnp.where(valid[:, None], xf[tok_src], 0.0))
    send_e = jnp.zeros((ep, cap), jnp.int32)
    send_e = send_e.at[safe_d, safe_p].set(
        jnp.where(valid, e_loc.reshape(-1), 0))
    # ---- Stage-2 dispatch ----
    recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis, 0, 0, tiled=False)
    rx = recv_x.reshape(ep * cap, D)
    re = recv_e.reshape(ep * cap)
    order = jnp.argsort(re)
    gs = jnp.bincount(re, length=E_loc)
    y_sorted = _expert_ffn(w_in, w_gate, w_out, rx[order], gs)
    y = jnp.zeros_like(rx).at[order].set(y_sorted)
    # ---- Stage-2 combine ----
    back = jax.lax.all_to_all(y.reshape(ep, cap, D), axis, 0, 0, tiled=False)
    picked = back[safe_d, safe_p]                           # [N*K, D]
    picked = jnp.where(valid[:, None], picked, 0.0)
    w = gates.reshape(-1)[:, None].astype(picked.dtype)
    out = jnp.zeros_like(xf).at[tok_src].add(picked * w)
    return out


def moe_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx,
              capacity_factor: float = 1.25, mode: str = "train"):
    """Expert-parallel MoE over ``ctx.ep_axes``.

    * ``("model",)`` — classic EP: experts sharded 16-way, all_to_all over
      the model axis (the paper's Stage-2 traffic).
    * ``("data", "model")`` — pod-wide 2D EP for models whose expert bank
      cannot fit a 16-way shard (DeepSeek-V3): experts spread over all 256
      chips, token dispatch over the combined axis. Prefill/train token
      grids are (batch x seq)-distinct per chip, so the same dispatch code
      serves both regimes; decode replicates the token batch inside the EP
      domain and combines partial expert outputs with a psum.
    """
    B, T, D = x.shape
    m = ctx.model_size
    ep = ctx.ep_size
    ep_axes = ctx.ep_axes if len(ctx.ep_axes) > 1 else ctx.ep_axes[0]
    batch = (ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0])
    expert_spec = P(ep_axes)
    if ctx.mesh is None or ep == 1 or cfg.n_experts % ep != 0:
        local = _moe_token_gather if mode == "decode" else _moe_local
        y = ctx.act(local(p, x, cfg), ("batch", None, None))
    elif T % m == 0:
        # prefill/train: sequence-split tokens, explicit dispatch+combine a2a
        def body(xl, router, w_in, w_gate, w_out):
            xf = xl.reshape(-1, D)
            out = _moe_ep_body(xf, router, w_in, w_gate, w_out, cfg=cfg,
                               axis=ep_axes,
                               capacity_factor=capacity_factor)
            return out.reshape(xl.shape)

        mapped = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(batch, ctx.model_axis, None),
                      P(), expert_spec, expert_spec, expert_spec),
            out_specs=P(batch, ctx.model_axis, None))
        y = mapped(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
        y = ctx.act(y, ("batch", None, None))
    else:
        # decode: tokens replicated inside the EP domain, masked local
        # compute + psum over the EP axes (Stage-2 combine)
        dec_batch_axes = tuple(a for a in ctx.batch_axes
                               if a not in ctx.ep_axes)
        dec_batch = (dec_batch_axes if len(dec_batch_axes) > 1 else
                     (dec_batch_axes[0] if dec_batch_axes else None))

        def body_dec(xl, router, w_in, w_gate, w_out):
            xf = xl.reshape(-1, D)
            N, K = xf.shape[0], cfg.top_k
            E_loc = w_in.shape[0]
            gates, idx = _route(xf, router, K)
            lo = _axis_index(ctx.ep_axes) * E_loc
            local = (idx >= lo) & (idx < lo + E_loc)
            flat_local = local.reshape(-1)
            e_loc = jnp.where(flat_local, (idx - lo).reshape(-1), E_loc)
            xin = jnp.where(flat_local[:, None], jnp.repeat(xf, K, axis=0), 0.0)
            order = jnp.argsort(e_loc)
            gs_full = jnp.bincount(e_loc, length=E_loc + 1)
            gs = jnp.concatenate([gs_full[:E_loc],
                                  gs_full[E_loc:E_loc + 1]])
            w_in_p = jnp.concatenate([w_in, jnp.zeros_like(w_in[:1])], 0)
            w_g_p = jnp.concatenate([w_gate, jnp.zeros_like(w_gate[:1])], 0)
            w_o_p = jnp.concatenate([w_out, jnp.zeros_like(w_out[:1])], 0)
            y_sorted = _expert_ffn(w_in_p, w_g_p, w_o_p, xin[order], gs)
            y = jnp.zeros_like(xin).at[order].set(y_sorted)
            wgt = gates.reshape(-1)[:, None].astype(y.dtype)
            out = jnp.zeros_like(xf).at[jnp.arange(N * K) // K].add(y * wgt)
            out = jax.lax.psum(out, ctx.ep_axes)            # Stage-2 combine
            return out.reshape(xl.shape)

        mapped = jax.shard_map(
            body_dec, mesh=ctx.mesh,
            in_specs=(P(dec_batch, None, None),
                      P(), expert_spec, expert_spec, expert_spec),
            out_specs=P(dec_batch, None, None))
        y = mapped(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
        y = ctx.act(y, ("batch", None, None))
    if "shared" in p:
        y = y + ffn_apply({"wi": p["shared"]["wi"], "wg": p["shared"]["wg"],
                           "wo": p["shared"]["wo"]}, x, cfg=cfg, ctx=ctx)
    return y


# =====================================================================
# Mamba2 (SSD) mixer
# =====================================================================
def ssd_init(key, cfg: ArchConfig, ctx: ShardCtx, dtype=DEFAULT_DTYPE):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    ks = jax.random.split(key, 4)
    return {
        # fused input projection: [z, x, B, C, dt]
        "w_in": init_dense(ks[0], d, 2 * d_in + 2 * N + H, dtype),
        "conv": (jax.random.normal(ks[1], (cfg.ssm_conv, d_in + 2 * N),
                                   jnp.float32) * 0.2).astype(dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": rmsnorm_params(d_in),
        "w_out": init_dense(ks[3], d_in, d, dtype),
    }


def _ssd_scan(xbc_dt, cfg: ArchConfig, init_state=None):
    """Sequential SSD recurrence via lax.scan over time (reference path; the
    Pallas chunked kernel is the TPU fast path). Returns (y, final_state)."""
    x, Bm, Cm, dt, A, D = xbc_dt                      # shapes below
    Bsz, T, H, hd = x.shape
    N = Bm.shape[-1]
    dA = jnp.exp(dt * A)                              # [B, T, H]
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, hd, N), jnp.float32)

    def step(s, inp):
        xt, Bt, Ct, dAt, dtt = inp                    # [B,H,hd],[B,N],[B,N],[B,H],[B,H]
        s = s * dAt[..., None, None] + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        yt = jnp.einsum("bhdn,bn->bhd", s, Ct)
        return s, yt

    xs = (x.transpose(1, 0, 2, 3), Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2),
          dA.transpose(1, 0, 2), dt.transpose(1, 0, 2))
    final, ys = jax.lax.scan(step, init_state, xs)
    y = ys.transpose(1, 0, 2, 3) + x * D[None, None, :, None]
    return y, final


def ssd_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx, mode: str,
              cache: Optional[Dict] = None, pos=0, window: int = 0):
    B, T, D = x.shape
    d_in = cfg.ssm_expand * D
    H = d_in // cfg.ssm_head_dim
    hd = cfg.ssm_head_dim
    N = cfg.ssm_state
    zxbcdt = dense(p["w_in"], x)
    z, xin, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + N, 2 * d_in + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)     # [B, T, d_in+2N]
    W = cfg.ssm_conv
    if mode == "decode":
        prev = cache["conv"]                              # [B, W-1, d_in+2N]
        window_seq = jnp.concatenate([prev, conv_in], axis=1)
        new_conv = window_seq[:, 1:]
    elif cache is not None and mode == "prefill":
        # suffix prefill: resume the conv window + SSD state from the prefix
        window_seq = jnp.concatenate([cache["conv"], conv_in], axis=1)
        new_conv = window_seq[:, T:]
    else:
        pad = jnp.zeros((B, W - 1, conv_in.shape[-1]), conv_in.dtype)
        window_seq = jnp.concatenate([pad, conv_in], axis=1)
        new_conv = window_seq[:, T:]                      # last W-1 entries
    kernel = p["conv"].astype(jnp.float32)                # [W, C]
    idx = jnp.arange(T)[:, None] + jnp.arange(W)[None, :]
    win = window_seq.astype(jnp.float32)[:, idx]          # [B, T, W, C]
    conv_out = jax.nn.silu(jnp.einsum("btwc,wc->btc", win, kernel))
    xc, Bc, Cc = jnp.split(conv_out, [d_in, d_in + N], axis=-1)
    xh = xc.reshape(B, T, H, hd)
    A = -jnp.exp(p["A_log"])                              # [H]
    dt_s = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    prev_state = cache["state"] if cache is not None else None
    from ..kernels import ops as kops
    y, state = kops.ssd(xh, Bc, Cc, dt_s, A, p["D"], init_state=prev_state,
                        ref_fallback=partial(_ssd_scan, cfg=cfg))
    y = y.reshape(B, T, d_in).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z))
    out = dense(p["w_out"], y)
    new_cache = {"conv": new_conv, "state": state} \
        if mode in ("prefill", "decode") else None
    return ctx.act(out, ("batch", None, None)), new_cache


# =====================================================================
# RG-LRU mixer (RecurrentGemma / Griffin recurrent block)
# =====================================================================
_RGLRU_BLOCKS = 16          # Griffin's block-diagonal gate heads; also the
                            # width-sharding granularity over "model"


def rglru_init(key, cfg: ArchConfig, ctx: ShardCtx, dtype=DEFAULT_DTYPE):
    d = cfg.d_model
    w = cfg.rglru_width or d
    nb = _RGLRU_BLOCKS if w % _RGLRU_BLOCKS == 0 else 1
    kb = w // nb
    ks = jax.random.split(key, 6)
    c = 8.0
    scale = 1.0 / math.sqrt(kb)
    return {
        "w_x": init_dense(ks[0], d, w, dtype),
        "w_gate_branch": init_dense(ks[1], d, w, dtype),
        "conv": (jax.random.normal(ks[2], (cfg.ssm_conv, w), jnp.float32)
                 * 0.2).astype(dtype),
        # block-diagonal gates (Griffin): [nb, kb, kb] — shards over the
        # model axis with zero gate collectives (§Perf iteration: the dense
        # [w, w] gates forced either 16x replicated compute or per-layer
        # all-reduces of [B,T,w])
        "gate_in": (jax.random.normal(ks[3], (nb, kb, kb), jnp.float32)
                    * scale).astype(dtype),
        "gate_rec": (jax.random.normal(ks[4], (nb, kb, kb), jnp.float32)
                     * scale).astype(dtype),
        # Lambda parametrised per-channel in (softplus space)
        "a_param": jnp.log(jnp.expm1(
            jnp.linspace(0.9, 0.999, w) ** (1.0 / c))).astype(jnp.float32),
        "w_out_rg": init_dense(jax.random.fold_in(key, 9), w, d, dtype),
    }


def rglru_apply(p, x, *, cfg: ArchConfig, ctx: ShardCtx, mode: str,
                cache: Optional[Dict] = None, pos=0, window: int = 0):
    B, T, D = x.shape
    w = cfg.rglru_width or D
    c = 8.0
    branch = ctx.act(dense(p["w_x"], x), ("batch", None, "model"))
    gate_branch = jax.nn.gelu(dense(p["w_gate_branch"], x))
    gate_branch = ctx.act(gate_branch, ("batch", None, "model"))
    # temporal conv on the branch
    W = cfg.ssm_conv
    if mode == "decode":
        seq = jnp.concatenate([cache["conv"], branch], axis=1)
        new_conv = seq[:, 1:]
    elif cache is not None and mode == "prefill":
        # suffix prefill: resume conv window + recurrent state from prefix
        seq = jnp.concatenate([cache["conv"], branch], axis=1)
        new_conv = seq[:, T:]
    else:
        pad = jnp.zeros((B, W - 1, w), branch.dtype)
        seq = jnp.concatenate([pad, branch], axis=1)
        new_conv = seq[:, T:]
    idx = jnp.arange(T)[:, None] + jnp.arange(W)[None, :]
    win = seq.astype(jnp.float32)[:, idx]
    xt = jnp.einsum("btwc,wc->btc", win, p["conv"].astype(jnp.float32))
    xt = ctx.act(xt, ("batch", None, "model"))
    # block-diagonal gates: shard-local einsum over the width blocks
    nb, kb = p["gate_rec"].shape[0], p["gate_rec"].shape[1]
    xtb = xt.astype(x.dtype).reshape(B, T, nb, kb)
    rt = jax.nn.sigmoid(jnp.einsum("btnk,nkj->btnj", xtb, p["gate_rec"])
                        .reshape(B, T, w).astype(jnp.float32))
    it = jax.nn.sigmoid(jnp.einsum("btnk,nkj->btnj", xtb, p["gate_in"])
                        .reshape(B, T, w).astype(jnp.float32))
    log_a = -c * rt * jax.nn.softplus(p["a_param"])        # [B, T, w]
    a = jnp.exp(log_a)
    gated_x = xt * it
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    prev = cache["state"] if cache is not None else None
    from ..kernels import ops as kops
    h, state = kops.rglru(a, beta * gated_x, init_state=prev)
    y = dense(p["w_out_rg"], (h.astype(x.dtype) * gate_branch))
    new_cache = {"conv": new_conv, "state": state} \
        if mode in ("prefill", "decode") else None
    return ctx.act(y, ("batch", None, None)), new_cache
