"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracles,
swept over shapes and dtypes per the brief."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.ssd_scan import ssd_chunked
from repro.kernels.rglru import rglru_scan
from repro.kernels import ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,T,S,H,D", [
    (2, 64, 64, 4, 64),
    (1, 200, 200, 3, 128),
    (2, 17, 300, 2, 64),      # ragged + chunked-prefill offset
    (1, 128, 128, 2, 96),     # non-128 head dim
    (1, 257, 257, 1, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, T, S, H, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, T, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    qoff = S - T
    out = flash_attention(q, k, v, causal=True, q_offset=qoff, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    q = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.normal(size=(2, 64, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 80, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 80, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_blockwise_xla_path_matches_full():
    """The XLA blockwise scan (dry-run lowering path) is exact."""
    q = jnp.asarray(RNG.normal(size=(1, 300, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 300, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 300, 2, 64)), jnp.float32)
    a = ref.blockwise_attention_ref(q, k, v, causal=True, block_q=64,
                                    block_k=64)
    b = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# -------------------------------------------------------------- decode attn
@pytest.mark.parametrize("B,S,H,D", [
    (2, 256, 4, 64), (3, 1000, 5, 128), (1, 128, 16, 64), (2, 513, 2, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, H, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, H, D)), dtype)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=(B,)), jnp.int32)
    out = decode_attention(q, k, v, lengths, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_length_one():
    q = jnp.asarray(RNG.normal(size=(2, 4, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 64, 4, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 64, 4, 64)), jnp.float32)
    lengths = jnp.asarray([1, 64], jnp.int32)
    out = decode_attention(q, k, v, lengths, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------- ssd
@pytest.mark.parametrize("Bz,T,H,hd,N,chunk", [
    (2, 64, 4, 64, 32, 32),
    (1, 100, 2, 64, 128, 32),    # ragged T
    (2, 256, 8, 64, 64, 128),
    (1, 32, 2, 128, 64, 16),
])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_chunked(Bz, T, H, hd, N, chunk, with_init):
    x = jnp.asarray(RNG.normal(size=(Bz, T, H, hd)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(Bz, T, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    s0 = (jnp.asarray(RNG.normal(size=(Bz, H, hd, N)), jnp.float32)
          if with_init else None)
    y, sf = ssd_chunked(x, Bm, Cm, dt, A, D, init_state=s0, chunk=chunk,
                        interpret=True)
    yr, sr = ref.ssd_ref(x, Bm, Cm, dt, A, D, init_state=s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               atol=1e-4, rtol=1e-4)


def test_ssd_state_chains_across_calls():
    """Splitting a sequence across two kernel calls == one long call."""
    Bz, T, H, hd, N = 1, 64, 2, 64, 32
    x = jnp.asarray(RNG.normal(size=(Bz, T, H, hd)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(Bz, T, H)), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    D = jnp.zeros((H,), jnp.float32)
    y_full, s_full = ssd_chunked(x, Bm, Cm, dt, A, D, chunk=32,
                                 interpret=True)
    h = T // 2
    y1, s1 = ssd_chunked(x[:, :h], Bm[:, :h], Cm[:, :h], dt[:, :h], A, D,
                         chunk=32, interpret=True)
    y2, s2 = ssd_chunked(x[:, h:], Bm[:, h:], Cm[:, h:], dt[:, h:], A, D,
                         init_state=s1, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


# -------------------------------------------------------------------- rglru
@pytest.mark.parametrize("B,T,W", [(2, 64, 256), (1, 200, 100), (3, 33, 512)])
@pytest.mark.parametrize("with_init", [False, True])
def test_rglru(B, T, W, with_init):
    a = jnp.asarray(RNG.uniform(0.7, 0.999, size=(B, T, W)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, T, W)), jnp.float32)
    s0 = (jnp.asarray(RNG.normal(size=(B, W)), jnp.float32)
          if with_init else None)
    h, sf = rglru_scan(a, x, init_state=s0, chunk=64, interpret=True)
    hr, sr = ref.rglru_ref(a, x, init_state=s0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr), atol=1e-4)


def test_rglru_decay_semantics():
    """a == 0 wipes history; a == 1 accumulates exactly."""
    B, T, W = 1, 16, 128
    x = jnp.ones((B, T, W), jnp.float32)
    h0, _ = rglru_scan(jnp.zeros((B, T, W)), x, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(h0), np.ones((B, T, W)), atol=1e-6)
    h1, s1 = rglru_scan(jnp.ones((B, T, W)), x, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(h1[0, -1]),
                               np.full((W,), T, np.float32), atol=1e-5)


# ---------------------------------------------------- flash custom-VJP (XLA)
@pytest.mark.slow
def test_flash_xla_forward_and_grads():
    """The production non-TPU flash path (custom VJP) matches the oracle in
    both value and gradients."""
    from repro.kernels.flash_xla import flash_attention_xla
    for (B, T, S, H, D, causal, window, qoff) in [
            (2, 128, 128, 2, 64, True, 0, 0),
            (1, 200, 300, 2, 64, True, 0, 100),
            (1, 256, 256, 2, 64, True, 64, 0)]:
        q = jnp.asarray(RNG.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, H, D)), jnp.float32)
        scale = 1.0 / np.sqrt(D)
        f = lambda *a: flash_attention_xla(*a, scale, causal, window,
                                           qoff, 64, 64)
        g = lambda *a: ref.flash_attention_ref(
            *a, causal=causal, window=window, q_offset=qoff)
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(g(q, k, v)), atol=3e-5)
        do = jnp.asarray(RNG.normal(size=(B, T, H, D)), jnp.float32)
        gf = jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(q, k, v)
        gg = jax.grad(lambda *a: jnp.sum(g(*a) * do), (0, 1, 2))(q, k, v)
        for a, b in zip(gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)


# --------------------------------------------------------- SSD dual (train)
@pytest.mark.slow
def test_ssd_dual_matches_recurrence():
    """The chunked dual (matmul) form — the memory-safe train path — is the
    same map as the sequential recurrence, values and grads."""
    rng = np.random.default_rng(3)
    for (Bz, T, H, hd, N, init) in [(2, 64, 4, 32, 32, False),
                                    (1, 100, 2, 64, 64, True)]:
        x = jnp.asarray(rng.normal(size=(Bz, T, H, hd)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(Bz, T, N)) * 0.5, jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (Bz, T, H)), jnp.float32)
        A = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
        s0 = (jnp.asarray(rng.normal(size=(Bz, H, hd, N)), jnp.float32)
              if init else None)
        y1, s1 = ref.ssd_ref(x, Bm, Cm, dt, A, D, init_state=s0)
        y2, s2 = ref.ssd_dual(x, Bm, Cm, dt, A, D, init_state=s0, chunk=32)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-3, rtol=1e-3)
        g1 = jax.grad(lambda xx: jnp.sum(
            ref.ssd_ref(xx, Bm, Cm, dt, A, D, init_state=s0)[0] ** 2))(x)
        g2 = jax.grad(lambda xx: jnp.sum(
            ref.ssd_dual(xx, Bm, Cm, dt, A, D, init_state=s0,
                         chunk=32)[0] ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-2, rtol=1e-2)


@pytest.mark.slow
def test_decode_step_time_calibrated_against_kernel_roofline():
    """`StageProfile.decode_step_time` (the smooth analytic model the decode
    plane schedules with) must track the roofline derived from the decode
    kernel's ACTUAL tiling (`decode_attention_cost`: 128-lane head padding,
    block_k KV padding, compute-skipped tail blocks, counted attention
    flops) within a tight relative error — including context lengths that
    straddle block boundaries, where the kernel pays for padding the model
    ignores."""
    from repro.kernels.decode_attention import decode_attention_cost
    from repro.core.stages import GroupPlan, ParallelismSpec, StageProfile
    from repro.simcluster.hw import A100
    from repro.simcluster.papermodels import PAPER_MODELS

    # the cost mirror must track the real kernel's launch math: run the
    # kernel once (interpret) at an off-block context and check the mirror
    # counted exactly the touched KV blocks
    B, H, D, S = 2, 4, 64, 300
    q = jnp.zeros((B, H, D), jnp.float32)
    k = v = jnp.zeros((B, S, H, D), jnp.float32)
    out = decode_attention(q, k, v, jnp.array([300, 10], jnp.int32),
                           interpret=True, block_k=256)
    assert out.shape == (B, H, D)
    fl, by = decode_attention_cost(1, H, D, 300, block_k=256, dtype_bytes=4)
    # ctx=300 pads to 2 x 256-blocks of 128-lane-padded heads
    assert by == 2 * 2 * 256 * H * 128 * 4 + 2 * H * 128 * 4
    assert fl == 2 * 4.0 * H * 128 * 256

    m = PAPER_MODELS["mixtral-8x7b"]
    prof = StageProfile(m, A100, ParallelismSpec(mode="ep", ep=4),
                        GroupPlan.build(m.n_layers, 8))
    errs = []
    for n in (1, 4, 16, 64):
        for ctx in (200, 1000, 3000, 4096, 20000):
            a = prof.decode_step_time(n, ctx)
            r = prof.decode_step_roofline(n, ctx)
            errs.append(abs(a - r) / r)
            # padding and attention flops only ever ADD work
            assert r >= a * (1 - 1e-9)
    assert max(errs) < 0.15, f"decode model error {max(errs):.3f}"


@pytest.mark.parametrize("env", [{"REPRO_USE_PALLAS": "0"},
                                 {"REPRO_PALLAS_INTERPRET": "1"}])
def test_tpu_backend_refuses_kernel_switches(monkeypatch, env):
    """On a TPU backend the compiled kernels are the only path: a switch to
    the jnp reference or to interpret mode raises instead of taking it."""
    from repro.kernels import ops
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 8, 1, 64), jnp.float32)
    with pytest.raises(RuntimeError):
        ops.attention(q, q, q)
