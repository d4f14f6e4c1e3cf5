"""The generator: the same work on every seed, in another order."""
import json
from collections import Counter

import numpy as np
import pytest

from chipbench import traffic
from chipbench.spec import HERE

MIXES = sorted(p.stem for p in (HERE / "workloads").glob("*.json"))


def _mix(name):
    return json.loads((HERE / "workloads" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    a = traffic.generate(_mix(name), 2**31 + 7, 20.0, 1000)
    b = traffic.generate(_mix(name), 2**31 + 7, 20.0, 1000)
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    assert all(np.array_equal(x.tokens, y.tokens)
               for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_schedule(name):
    mix = _mix(name)
    a = traffic.generate(mix, 1, 30.0, 1000)
    b = traffic.generate(mix, 99999999999, 30.0, 1000)
    n = round(mix["rate"] * 30.0)
    assert len(a.requests) == len(b.requests) == n
    for f in (lambda r: r.due, lambda r: len(r.tokens), lambda r: r.max_new,
              lambda r: r.new_len, lambda r: r.context):
        assert list(map(f, a.requests)) == list(map(f, b.requests))
    assert not any(np.array_equal(x.tokens, y.tokens)
                   for x, y in zip(a.requests, b.requests))
    assert max(r.due for r in a.requests) < 30.0


@pytest.mark.parametrize("name", MIXES)
def test_warmup_covers_every_shape(name):
    mix = _mix(name)
    tr = traffic.generate(mix, 5, 60.0, 1000)
    seen = {(len(r.tokens), r.new_len) for r in tr.requests}
    warm = {(len(r.tokens), r.new_len) for r in tr.warmup}
    assert seen <= warm
    assert len(warm) == len(traffic.shapes(mix))


def test_shared_contexts_start_every_prompt():
    mix = _mix("smollm-360m.code-reuse")
    tr = traffic.generate(mix, 3, 30.0, 1000)
    assert len(tr.contexts) == 16
    for r in tr.requests + tr.warmup:
        c = tr.contexts[r.context]
        assert np.array_equal(r.tokens[:len(c)], c)
        assert len(r.tokens) == len(c) + r.new_len


def test_unique_prompts_share_nothing():
    mix = _mix("smollm-360m.code-reuse")
    mix["contexts"] = dict(mix["contexts"], shared=False)
    tr = traffic.generate(mix, 3, 30.0, 50000)
    assert not tr.contexts
    heads = {r.tokens[:16].tobytes() for r in tr.requests}
    assert len(heads) == len(tr.requests)


def test_gaps_mean_and_burstiness():
    g = traffic.gaps({"process": "gamma", "cv": 3.0}, 400, 200.0)
    assert np.isclose(g.mean(), 0.5)
    assert 2.0 < g.std() / g.mean() < 3.5          # stratified tail is cut
    p = traffic.gaps({"process": "poisson"}, 400, 100.0)
    assert np.isclose(p.mean(), 0.25)
    assert 0.9 < p.std() / p.mean() < 1.1


def test_shares_and_output_quantiles():
    assert Counter(traffic._shares({128: 0.75, 512: 0.25}, 10)) == \
        Counter({128: 8, 512: 2})
    out = traffic.output_lengths(
        {"median": 16, "sigma": 1.0, "min": 4, "max": 256}, 101)
    assert np.median(out) == 16 and out.min() >= 4 and out.max() <= 256
