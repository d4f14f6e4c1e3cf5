"""Numerical sharded-vs-single-device equivalence (run as a SUBPROCESS by
test_sharded.py — needs its own jax process to pin 8 virtual devices).

Checks, on a (2 data x 4 model) CPU mesh:
  * dense GQA (smollm):   loss + prefill logits match unsharded
  * MoE classic EP:       dispatch/combine all_to_all path matches local
  * MoE 2D EP:            combined ("data","model") dispatch matches local
  * MoE decode:           psum-over-EP-axes path matches local
  * MLA (dsv3 smoke):     loss matches
Exit code 0 = all pass.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import sys

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import SMOKES
from repro.launch.mesh import auto_mesh
from repro.models.lm import build_model
from repro.models.sharding import ShardCtx

TOL = 3e-2          # bf16 params; collective reductions reorder sums


def _check(name, a, b, tol=TOL):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = max(1e-6, float(np.max(np.abs(a))))
    err = float(np.max(np.abs(a - b))) / scale
    status = "OK " if err < tol else "FAIL"
    print(f"{status} {name:42s} rel_err={err:.2e}")
    return err < tol


def main() -> int:
    assert jax.device_count() == 8, jax.device_count()
    mesh = auto_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    ok = True

    # ---------------- dense GQA ----------------
    cfg = SMOKES["smollm-360m"]
    ref_model = build_model(cfg, ShardCtx())
    params = ref_model.init(key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    want_loss = ref_model.loss(params, batch)
    want_logits, _ = ref_model.prefill(params, {"tokens": toks})

    sh_model = build_model(cfg, ShardCtx(mesh=mesh))
    got_loss = jax.jit(sh_model.loss)(params, batch)
    got_logits, _ = jax.jit(sh_model.prefill)(params, {"tokens": toks})
    ok &= _check("dense loss (2x4 mesh)", want_loss, got_loss)
    ok &= _check("dense prefill logits", want_logits, got_logits)

    # ---------------- MoE: classic EP over ("model",) ----------------
    cfg = SMOKES["deepseek-moe-16b"]
    ref_model = build_model(cfg, ShardCtx())
    params = ref_model.init(key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    want = ref_model.loss(params, batch)
    ep_model = build_model(cfg, ShardCtx(mesh=mesh, ep_axes=("model",)))
    got = jax.jit(ep_model.loss)(params, batch)
    ok &= _check("MoE classic EP loss (a2a over model)", want, got,
                 tol=6e-2)   # capacity-dropped tokens may differ slightly

    # ---------------- MoE: 2D EP over ("data","model") ----------------
    ep2_model = build_model(cfg, ShardCtx(mesh=mesh,
                                          ep_axes=("data", "model")))
    got2 = jax.jit(ep2_model.loss)(params, batch)
    ok &= _check("MoE 2D EP loss (a2a over data+model)", want, got2,
                 tol=6e-2)

    # ---------------- MoE decode: psum path ----------------
    _, cache = ref_model.prefill(params, {"tokens": toks})
    tok = toks[:, :1]
    want_d, _ = ref_model.decode_step(params, _grow(cache), tok, 16)
    got_d, _ = jax.jit(ep_model.decode_step)(params, _grow(cache), tok, 16)
    ok &= _check("MoE decode (psum over EP axes)", want_d, got_d)

    # ---------------- MLA (dsv3 smoke) ----------------
    cfg = SMOKES["deepseek-v3-671b"]
    ref_model = build_model(cfg, ShardCtx())
    params = ref_model.init(key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    batch = {"tokens": toks, "labels": toks, "labels2": toks}
    want = ref_model.loss(params, batch)
    sh_model = build_model(cfg, ShardCtx(mesh=mesh))
    got = jax.jit(sh_model.loss)(params, batch)
    ok &= _check("MLA + MoE + MTP loss", want, got, tol=6e-2)

    return 0 if ok else 1


def _grow(cache):
    def f(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if name in ("k", "v", "c", "kr"):
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, 8)
            return jnp.pad(leaf, pad)
        return leaf
    return jax.tree_util.tree_map_with_path(f, cache)


if __name__ == "__main__":
    sys.exit(main())
