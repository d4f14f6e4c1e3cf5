"""Plain float32 reference of a dense GQA decoder, layer by layer.

It imports nothing of the program. It draws the model's random weights
from the seed itself, by the scheme the served model is initialised with
(``draw_*`` below), and runs the forward pass in float32 with
``precision=highest`` matmuls, one layer at a time over the sampled
sequences as one batch, queries in blocks, so that it fits on the chip
once the served model is freed.

The decoder it computes: token embedding; per layer, pre-RMSNorm (gain 1),
GQA attention with rotary position embedding (half-split rotation) and a
causal mask, a residual, pre-RMSNorm and a gated SiLU MLP
(``(silu(x Wg) * (x Wi)) Wo``), a residual; a final RMSNorm and the
logits (tied: the embedding's transpose). No biases.

It is the reference of the dense decoder family, whose module in
``families/`` hands its ``logits`` here and whose ``dims`` refuses a
configuration that asks for anything else; another family brings its own
reference. The query heads the program pads are left out here (below).
``params_key``, the seed's key of the served weights, is the program's and
serves every family.

The weight scheme (what the seed means): ``k = PRNGKey(seed)``, split into
64; the embedding ``[Vp, d]`` from key 0; an untied unembedding ``[d, Vp]``
from key 1; the layer stack from the next key, split into one key per
layer, each split into 1 and then 4 (attention, MLP, -, -); attention keys
split into 4 for ``Wq [d, Hp*hd]``, ``Wk``, ``Wv [d, Hkv*hd]``,
``Wo [Hp*hd, d]``; MLP keys into 3 for ``Wi``, ``Wg [d, F]``, ``Wo [F, d]``.
Each matrix is a standard normal in float32 times ``1/sqrt(rows)``, rounded
to bfloat16. ``Hp`` is the query heads padded to a multiple of 16 and
``Vp`` the vocabulary padded likewise; the padded heads' rows of the
attention output matrix are zero, so the reference keeps the real heads.

``fp8=True`` is the control: every weight matmul takes its inputs rounded
to float8 e4m3 (per-row scales for activations, per-column for weights),
the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HEAD_PAD = 16
Q_BLOCK = 512
LEN_BUCKET = 512


def _pad(n: int, m: int = HEAD_PAD) -> int:
    return -(-n // m) * m


def params_key(seed: int):
    """The key the served model's weights are drawn from."""
    s = int(np.random.SeedSequence(seed).generate_state(1, np.uint32)[0])
    return jax.random.PRNGKey(s)


def _normal(key, shape, rows: int):
    x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(rows)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@partial(jax.jit, static_argnames=("m",))
def _draw_embed(key, m):
    ks = jax.random.split(key, 64)
    vp, d = _pad(m["vocab"]), m["d_model"]
    emb = _normal(ks[0], (vp, d), d)[:m["vocab"]]
    if m["tied"]:
        return emb, emb.T
    unemb = _normal(ks[1], (d, vp), d)[:, :m["vocab"]]
    return emb, unemb


def _layer_keys(key, m):
    ks = jax.random.split(key, 64)
    return jax.random.split(ks[1 if m["tied"] else 2], m["n_layers"])


@partial(jax.jit, static_argnames=("m",))
def _draw_layer(layer_key, m) -> Dict[str, jnp.ndarray]:
    d, h, hd, f = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    hp = _pad(h)
    kv = hp if m["n_kv"] == h else m["n_kv"]
    sub = jax.random.split(jax.random.split(layer_key, 1)[0], 4)
    kq, kk, kv_, ko = jax.random.split(sub[0], 4)
    k1, k2, k3 = jax.random.split(sub[1], 3)
    return {
        "wq": _normal(kq, (d, hp * hd), d)[:, :h * hd],
        "wk": _normal(kk, (d, kv * hd), d)[:, :m["n_kv"] * hd],
        "wv": _normal(kv_, (d, kv * hd), d)[:, :m["n_kv"] * hd],
        "wo": _normal(ko, (hp * hd, d), hp * hd)[:h * hd],
        "wi": _normal(k1, (d, f), d),
        "wg": _normal(k2, (d, f), d),
        "wo2": _normal(k3, (f, d), f),
    }


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate halves by angle pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer_one(x, w, m, fp8: bool):
    """One decoder layer over one sequence x: [S, d] (float32)."""
    S = x.shape[0]
    h, kv, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    hi = jax.lax.Precision.HIGHEST
    pos = jnp.arange(S)
    a = _rms(x, m["norm_eps"])
    q = _rope(_mm(a, w["wq"], fp8).reshape(S, h, hd), pos, m["rope_theta"])
    k = _rope(_mm(a, w["wk"], fp8).reshape(S, kv, hd), pos, m["rope_theta"])
    v = _mm(a, w["wv"], fp8).reshape(S, kv, hd)
    q = q.reshape(S, kv, h // kv, hd) / math.sqrt(hd)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("tgrd,sgd->grts", qb, k, precision=hi)
        keep = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("grts,sgd->tgrd", p, v, precision=hi))
    o = jnp.concatenate(outs, 0).reshape(S, h * hd)
    x = x + _mm(o, w["wo"], fp8)
    b = _rms(x, m["norm_eps"])
    return x + _mm(jax.nn.silu(_mm(b, w["wg"], fp8)) * _mm(b, w["wi"], fp8),
                   w["wo2"], fp8)


@partial(jax.jit, static_argnames=("m", "fp8"), donate_argnums=(0,))
def _layer(x, w, m, fp8: bool):
    """One layer over a batch of sequences x: [K, S, d]."""
    return jax.vmap(lambda xi: _layer_one(xi, w, m, fp8))(x)


@partial(jax.jit, static_argnames=("m", "fp8"))
def _logits(x, rows, unemb, m, fp8: bool):
    """x: [K, S, d], rows: [K, R] -> [K, R, vocab]."""
    xr = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _mm(_rms(xr, m["norm_eps"]), unemb, fp8)


def logits(m: Dict, seed: int, seqs: Sequence[np.ndarray],
           rows: Sequence[np.ndarray], fp8: bool = False) -> List[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(rows), vocab]``
    at positions ``rows``. The sequences run as one batch, padded at the
    end to a common length (causal: the padding changes nothing before
    it). ``m`` is ``model.dims``."""
    mh = _Frozen(m)
    key = params_key(seed)
    S = _pad(max(len(s) for s in seqs), LEN_BUCKET)
    R = max(len(r) for r in rows)
    tok = np.zeros((len(seqs), S), np.int32)
    at = np.zeros((len(seqs), R), np.int32)
    for i, (s, r) in enumerate(zip(seqs, rows)):
        tok[i, :len(s)] = s
        at[i, :len(r)] = r
    with jax.default_matmul_precision("highest"):
        emb, unemb = _draw_embed(key, mh)
        x = jnp.take(emb, jnp.asarray(tok), axis=0)
        del emb
        for lk in _layer_keys(key, mh):
            x = _layer(x, _draw_layer(lk, mh), mh, fp8)
        out = np.asarray(_logits(x, jnp.asarray(at), unemb, mh, fp8))
    return [out[i, :len(r)] for i, r in enumerate(rows)]


class _Frozen(dict):
    """A dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
