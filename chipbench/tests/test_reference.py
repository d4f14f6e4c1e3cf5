"""The float32 reference draws the served model's weights from the seed
and computes what the served model computes (CPU, rehearsal sizes)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model, reference
from chipbench.spec import HERE

CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))
SEED = 2**31 + 12345


def _small(name):
    return model.shrunk(json.loads((HERE / "configs" / f"{name}.json")
                                   .read_text()))


@pytest.fixture(scope="module", params=CONFIGS)
def served(request):
    from repro.models.lm import build_model
    conf = _small(request.param)
    mdl = build_model(model.arch_config(conf))
    params = jax.jit(mdl.init)(reference.params_key(SEED))
    return model.dims(conf), mdl, params


def test_weights_are_the_served_models(served):
    m, mdl, p = served
    mh = reference._Frozen(m)
    emb, unemb = reference._draw_embed(reference.params_key(SEED), mh)
    np.testing.assert_array_equal(
        emb, np.asarray(p["embed"], np.float32)[:m["vocab"]])
    keys = reference._layer_keys(reference.params_key(SEED), mh)
    h, hd = m["n_heads"], m["head_dim"]
    for layer in (0, m["n_layers"] - 1):
        w = reference._draw_layer(keys[layer], mh)
        lp = jax.tree.map(lambda x: np.asarray(x[layer], np.float32),
                          p["seg0"][0])
        np.testing.assert_array_equal(w["wq"], lp["mix"]["wq"]["w"][:, :h * hd])
        np.testing.assert_array_equal(w["wk"], lp["mix"]["wk"]["w"])
        np.testing.assert_array_equal(w["wo"], lp["mix"]["wo"]["w"][:h * hd])
        assert not lp["mix"]["wo"]["w"][h * hd:].any()
        np.testing.assert_array_equal(w["wg"], lp["ffn"]["wg"]["w"])
        np.testing.assert_array_equal(w["wo2"], lp["ffn"]["wo"]["w"])


def test_logits_match_the_served_prefill(served):
    m, mdl, p = served
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, m["vocab"], n).astype(np.int32)
            for n in (37, 80)]
    ref = reference.logits(m, SEED, seqs, [np.array([len(s) - 1])
                                           for s in seqs])
    for s, r in zip(seqs, ref):
        got, _ = mdl.prefill(p, {"tokens": jnp.asarray(s[None])})
        got = np.asarray(got[0, -1], np.float32)
        # bfloat16 weights and activations against float32
        err = np.abs(got - r[0]).max() / np.abs(r[0]).max()
        assert err < 3e-2, err


def test_control_rounds_lower(served):
    m, _, _ = served
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, m["vocab"], 64).astype(np.int32)]
    rows = [np.arange(40, 64)]
    ref = reference.logits(m, SEED, seqs, rows)[0]
    ctl = reference.logits(m, SEED, seqs, rows, fp8=True)[0]
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5, err
