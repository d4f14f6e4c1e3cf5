"""The Pallas kernels compile for a TPU v5e chip at the widths of the configs
that reach them.

Nothing runs: each kernel is lowered and compiled for one chip of a
*described* ``v5e:2x2`` topology, from shapes alone. This catches what
interpret-mode tests cannot — Mosaic's block-tiling rule, unsupported vector
ops, VMEM limits. The topology is described inside a fixture (never while a
module is imported), so every xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan
from repro.kernels.ssd_scan import ssd_chunked
from repro.models.blocks import AttnDims
from repro.models.sharding import ShardCtx


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(shp, dt, sharding=shardings)
            for shp, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _heads(arch):
    """(padded query heads, head dim) as the model layer feeds the kernels."""
    cfg = ARCHS[arch]
    dims = AttnDims.of(cfg, ShardCtx())
    return dims.n_q, dims.hd


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


# starcoder2-3b prefill: a full 1152-token prompt, and a 128-token suffix over
# a reused 1024-token prefix (the two prefill shapes of chip_smoke.py)
@pytest.mark.parametrize("T,S,q_offset", [(1152, 1152, 0), (128, 1152, 1024)])
def test_flash_attention_compiles_starcoder2_3b(one_chip, T, S, q_offset):
    H, D = _heads("starcoder2-3b")
    _compile(lambda q, k, v: flash_attention(q, k, v, q_offset=q_offset),
             one_chip, ((1, T, H, D), BF16), ((1, S, H, D), BF16),
             ((1, S, H, D), BF16))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "smollm-360m"])
def test_decode_attention_compiles(one_chip, arch):
    H, D = _heads(arch)
    B, S = 8, 1280
    _compile(lambda q, k, v, n: decode_attention(q, k, v, n), one_chip,
             ((B, H, D), BF16), ((B, S, H, D), BF16), ((B, S, H, D), BF16),
             ((B,), I32))


@pytest.mark.parametrize("T,with_init", [(1024, False), (300, True)])
def test_ssd_chunked_compiles_mamba2_1_3b(one_chip, T, with_init):
    cfg = ARCHS["mamba2-1.3b"]
    hd, N = cfg.ssm_head_dim, cfg.ssm_state
    H = cfg.ssm_expand * cfg.d_model // hd
    shapes = [((1, T, H, hd), F32), ((1, T, N), F32), ((1, T, N), F32),
              ((1, T, H), F32), ((H,), F32), ((H,), F32)]
    if with_init:
        shapes.append(((1, H, hd, N), F32))
        fn = lambda x, b, c, dt, a, d, s: ssd_chunked(x, b, c, dt, a, d,
                                                      init_state=s)
    else:
        fn = lambda x, b, c, dt, a, d: ssd_chunked(x, b, c, dt, a, d)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("B,T,with_init", [(1, 1024, False), (2, 300, True),
                                           (8, 1, True)])
def test_rglru_scan_compiles_recurrentgemma_9b(one_chip, B, T, with_init):
    W = ARCHS["recurrentgemma-9b"].rglru_width
    shapes = [((B, T, W), F32), ((B, T, W), F32)]
    if with_init:
        shapes.append(((B, W), F32))
        fn = lambda a, x, s: rglru_scan(a, x, init_state=s)
    else:
        fn = lambda a, x: rglru_scan(a, x)
    _compile(fn, one_chip, *shapes)
