"""The control: the float8 reference put in the program's place comes out
not correct under each cell's own limit, where the served model comes out
correct (CPU, the configuration at its published sizes, greedy tokens of
the served model's prefill). On the chip, at the cells' sizes, the control
reads several times the limit and the served model a fraction of it
(PERF.md)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, model
from chipbench.reference import params_key
from chipbench.spec import ROOT, load_cell

SEEDS = [2**31 + 5, 7, 2**33 + 1]
CELLS = sorted(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])


def _cases():
    """One case per configuration and limit: cells that share both read
    the same numbers."""
    seen = {}
    for name in CELLS:
        cell = load_cell(name)
        seen.setdefault((cell.config["name"], check.limit(cell.traffic)),
                        name)
    return sorted(seen.values())


@pytest.mark.parametrize("name", _cases())
def test_control_is_not_correct_under_the_cells_limit(name):
    from repro.models.lm import build_model
    cell = load_cell(name)
    conf, limit = cell.config, check.limit(cell.traffic)
    m = model.dims(conf)
    mdl = build_model(model.arch_config(conf))
    prefill = jax.jit(mdl.prefill)
    for seed in SEEDS:
        params = jax.jit(mdl.init)(params_key(seed))
        prompt = np.random.default_rng(seed).integers(
            0, m["vocab"], 48).astype(np.int32)
        seq, toks = prompt, []          # the served model's greedy tokens
        for _ in range(24):
            lg, _ = prefill(params, {"tokens": jnp.asarray(seq[None])})
            toks.append(int(jnp.argmax(lg[0, -1])))
            seq = np.append(seq, toks[-1]).astype(np.int32)
        del params
        s = check.draw({0: (prompt, toks)}, seed, 100, 1)
        served = check.served_gap(m, seed, s)
        control = check.control_gap(m, seed, s)
        assert check.decide(served, limit), (seed, served, limit)
        assert not check.decide(control, limit), (seed, control, limit)
