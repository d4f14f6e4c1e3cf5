"""Each cell's served programs compile for a described v5e chip at full
width: the compiler refuses a program that does not fit the chip's memory.
The decode step's arguments (weights and the stacked slot cache) and the
page pools fit beside each other (no chip needed; libtpu's compiler runs
here). What the chip holds at its peak is read on the chip (PERF.md)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import model, traffic
from chipbench.spec import ROOT, load_cell

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CELLS = sorted(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
HBM = 15.75e9                   # what the compiler lets one v5e program use


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CELLS)
def test_prefill_and_decode_compile_and_fit(name, one_chip, monkeypatch):
    from repro.models.lm import build_model
    from repro.serving.engine import DecodeBatch, ServingEngine
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    # a rehearsal earlier in the process may have asked for interpret mode
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    cell = load_cell(name)
    conf, mix = cell.config, cell.traffic
    mdl = build_model(model.arch_config(conf))
    params = sds(jax.eval_shape(mdl.init, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))

    n = max(L + b for L, b in traffic.shapes(mix))
    tok = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one_chip)
    pre = ServingEngine(mdl, params)._full.lower(params,
                                                 {"tokens": tok}).compile()
    assert "tpu_custom_call" in pre.as_text()      # the flash kernel

    s = conf["serving"]
    db = DecodeBatch(mdl, params, capacity=s["decode_capacity"],
                     max_slots=s["decode_slots"])
    example = jax.eval_shape(
        lambda p, t: mdl.prefill(p, {"tokens": t})[1], params,
        jax.ShapeDtypeStruct((1, 16), jnp.int32))
    stacked = jax.eval_shape(lambda: (db._build(example), db._stacked)[1])
    k = s["decode_slots"]
    step = db._step_fn.lower(
        params, sds(stacked),
        jax.ShapeDtypeStruct((k, 1, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)).compile()
    ma = step.memory_analysis()
    per_token = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(example)) / 16
    pages = s["n_pages"] * s["page_size"] * per_token
    assert ma.argument_size_in_bytes >= weights
    assert ma.argument_size_in_bytes + pages < HBM, (weights, pages)
