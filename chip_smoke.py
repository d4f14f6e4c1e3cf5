"""Smoke run of the serving main path on a TPU chip.

    python chip_smoke.py              # one chip: the serve phase
    python chip_smoke.py --chips 4    # four chips: the sharded-model phase

Serve phase (one chip). ``DisaggServer.serve`` (router -> prefix index ->
``ServingEngine`` prefill -> ``DecodeBatch`` decode, scheduled by MFS)
serves 8 requests at starcoder2-3b's published widths with random weights
made from seed 0: two 1024-token prefixes, each prompt one of them plus
128 fresh tokens, one request per prefix first to warm the prefix index,
16 new tokens each. It passes only if the compiled Pallas kernels ran (TPU
backend, not interpret mode, ``tpu_custom_call`` in the prefill program),
every request got its 16 tokens, the six follow-ups reused a prefix, and
for two follow-ups the served reuse path's last-position logits match a
full recompute of the same prompt.

Sharded phase (``--chips 4``). The sharded model that ``launch/train.py``
runs on several chips: starcoder2-3b full-width prefill logits on a
(data=1, model=4) mesh against the unsharded model on one chip, and
deepseek-moe-16b (SMOKE) loss with expert-parallel ``all_to_all`` over
"model" against the local MoE path.

Lines before the last report what the run saw: device, compiles, wall times
of single steps and peak device memory. They are smoke readings, not
benchmark metrics. The last line is ``{"ok": true, "device": {...}}``. With
no TPU backend the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.configs import ARCHS, SMOKES                      # noqa: E402
from repro.core import make_policy                           # noqa: E402
from repro.core.telemetry import wall_spans                  # noqa: E402
from repro.kernels import ops                                # noqa: E402
from repro.launch.cache import enable_compile_cache          # noqa: E402
from repro.models.lm import build_model                      # noqa: E402
from repro.serving import (DisaggConfig, DisaggServer,       # noqa: E402
                           ServeRequest)

ARCH = "starcoder2-3b"
#: bf16 weights and activations; relative to the largest |logit|, as in
#: tests/sharded_check.py (collectives and cache reuse reorder bf16 sums)
LOGIT_TOL = 3e-2
MOE_TOL = 6e-2              # capacity-dropped tokens may differ slightly


def compile_line() -> str:
    """The process's XLA compiles, from the program's per-entry counter."""
    c = wall_spans.compiles
    t = c.total()
    entries = ", ".join(f"{n} {c.entry(n).compiles}" for n in
                        ("prefill_full", "prefill_suffix", "decode_step"))
    return (f"compiles: {t.compiles - t.cache_hits} xla compiles +"
            f" {t.cache_hits} persistent-cache hits,"
            f" {t.seconds:.1f} s in compile calls; by entry: {entries}")


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(1e-6, np.max(np.abs(a))))


def smoke_requests(vocab: int, *, seed: int, prefix_len: int,
                   suffix_len: int, max_new: int, per_prefix: int = 4):
    """Two shared prefixes; the first request of each warms the index, the
    follow-ups arrive after the warm-ups are done (on the virtual clock)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, prefix_len) for _ in range(2)]
    reqs = []
    for i in range(per_prefix):
        for pfx in prefixes:
            rid = len(reqs)
            toks = np.concatenate([pfx, rng.integers(0, vocab, suffix_len)])
            arrival = 0.0 if i == 0 else 1.0 + 0.01 * rid
            reqs.append(ServeRequest(rid=rid, arrival=arrival, tokens=toks,
                                     max_new=max_new))
    return reqs


def _timed(fn, log: list):
    """Wrap ``fn`` so each call is timed to device completion."""
    def wrapped(*a, **kw):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a, **kw))
        log.append(time.perf_counter() - t0)
        return out
    return wrapped


def _times(label: str, ts) -> str:
    if not ts:
        return f"{label}: no calls"
    warm = ", ".join(f"{t * 1e3:.2f}" for t in ts[1:])
    return (f"{label}: {len(ts)} calls; first {ts[0]:.3f} s (compile"
            f" included); later, ms: [{warm}]")


def serve_phase(cfg, *, seed: int = 0, prefix_len: int = 1024,
                suffix_len: int = 128, max_new: int = 16, hw=None,
                on_chip: bool = True, log=print) -> list:
    """Serve the smoke traffic once through ``DisaggServer``; return the
    names of the checks that failed (empty list: all passed). ``on_chip``
    also requires the compiled Pallas kernel in the prefill program."""
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    reqs = smoke_requests(cfg.vocab, seed=seed, prefix_len=prefix_len,
                          suffix_len=suffix_len, max_new=max_new)
    n_tok = prefix_len + suffix_len
    srv = DisaggServer(model, params, policy=make_policy("mfs"),
                       cfg=DisaggConfig(n_prefill_units=2, hw=hw,
                                        decode_slots=len(reqs),
                                        decode_capacity=n_tok + max_new + 2))

    # record what the served path computed, timed to device completion
    served, t_full, t_suffix, t_decode = {}, [], [], []
    prefill = srv.engine.prefill

    def recording_prefill(tokens, prefix_cache=None, prefix_len=0,
                          extra=None):
        t = t_suffix if prefix_cache is not None and prefix_len else t_full
        out = _timed(prefill, t)(tokens, prefix_cache=prefix_cache,
                                 prefix_len=prefix_len, extra=extra)
        served[np.asarray(tokens).tobytes()] = out[2]
        return out

    srv.engine.prefill = recording_prefill
    step = srv.decoder.step
    srv.decoder.step = lambda: _timed(
        lambda: (step(), srv.decoder._stacked), t_decode)()[0]

    t0 = time.perf_counter()
    res = srv.serve(reqs, decode_steps=max_new)
    log(f"serve wall: {time.perf_counter() - t0:.3f} s for {len(reqs)}"
        f" requests (compiles included)")
    log(_times(f"prefill, full {n_tok} tokens", t_full))
    log(_times(f"prefill, {suffix_len}-token suffix over reused prefix",
               t_suffix))
    log(_times(f"decode step, {len(reqs)} slots", t_decode))

    failed = []
    follow = [r for r in res if reqs[r.rid].arrival > 0]
    log("served: " + ", ".join(
        f"rid {r.rid}: {len(r.tokens)} tok, reused {r.reused_tokens}"
        for r in res))
    if not all(len(r.tokens) == max_new for r in res):
        failed.append(f"every request got {max_new} tokens")
    if not (len(follow) == 6 and all(r.reused_tokens > 0 for r in follow)):
        failed.append("six follow-ups reused a prefix")
    if not all(r.prefix_registered and r.decode_admitted for r in res):
        failed.append("every prefix registered and every request decoded")

    # reuse path vs full recompute of the same prompt, for one follow-up
    # per prefix (full recompute has the warm-up's shape: no new compile)
    for r in follow[:2]:
        toks = reqs[r.rid].tokens
        _, _, want = prefill(toks)
        err = rel_err(want, served[toks.tobytes()])
        log(f"rid {r.rid}: reuse vs recompute logits rel err {err:.2e}"
            f" (tol {LOGIT_TOL:g})")
        if not err < LOGIT_TOL:
            failed.append(f"rid {r.rid} reuse logits match recompute")

    if on_chip:
        # the program the full prefills ran (already compiled: no new one)
        hlo = srv.engine._full.lower(
            params, {"tokens": jnp.zeros((1, n_tok), jnp.int32)}).compile()
        if "tpu_custom_call" not in hlo.as_text():
            failed.append("tpu_custom_call in the compiled prefill")
    return failed


def sharded_phase(dense_cfg, *, seed: int = 0, n_tok: int = 1152,
                  log=print) -> list:
    """The sharded model on a (data=1, model=4) mesh of the first four
    devices, against the unsharded model on one device."""
    from repro.launch.mesh import auto_mesh
    from repro.launch.shardings import param_specs, to_shardings
    from repro.models.sharding import ShardCtx

    failed = []
    mesh = auto_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)

    cfg = dense_cfg
    ref = build_model(cfg)
    params = jax.jit(ref.init)(key)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (1, n_tok)),
                                   jnp.int32)}
    want, _ = jax.jit(ref.prefill)(params, batch)
    sh = build_model(cfg, ShardCtx(mesh=mesh))
    sh_params = jax.device_put(params, to_shardings(param_specs(sh), mesh))
    del params
    got, _ = jax.jit(sh.prefill)(sh_params, batch)
    err = rel_err(want, got)
    log(f"{cfg.name} prefill logits, (1 data x 4 model) mesh vs one device:"
        f" rel err {err:.2e} (tol {LOGIT_TOL:g})")
    if not err < LOGIT_TOL:
        failed.append(f"{cfg.name} sharded prefill logits")
    del sh_params

    cfg = SMOKES["deepseek-moe-16b"]
    ref = build_model(cfg)
    params = jax.jit(ref.init)(key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    want = jax.jit(ref.loss)(params, batch)
    ep = build_model(cfg, ShardCtx(mesh=mesh, ep_axes=("model",)))
    got = jax.jit(ep.loss)(params, batch)
    err = rel_err(want, got)
    log(f"deepseek-moe-16b (smoke) EP loss, all_to_all over 4 devices vs"
        f" local: rel err {err:.2e} (tol {MOE_TOL:g})")
    if not err < MOE_TOL:
        failed.append("deepseek-moe-16b EP loss")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-model phase on four chips")
    a = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU backend (JAX found {backend!r});"
              " nothing was run", file=sys.stderr)
        return 1
    if not ops.use_pallas() or ops.interpret_mode():
        print("chip_smoke: Pallas kernels are not compiled", file=sys.stderr)
        return 1
    devs = jax.devices()
    if len(devs) < a.chips:
        print(f"chip_smoke: --chips {a.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x {len(devs)};"
          f" compile cache {cache_dir}", flush=True)

    log = lambda s: print(s, flush=True)
    if a.chips == 4:
        failed = sharded_phase(ARCHS[ARCH], log=log)
    else:
        failed = serve_phase(ARCHS[ARCH], log=log)
    log(compile_line())
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    if failed:
        print("chip_smoke: FAILED: " + "; ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
