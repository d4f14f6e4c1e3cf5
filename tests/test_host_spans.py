"""Wall-clock span channel (``repro.core.telemetry.wall_spans``): it records
exactly while a profiler session is active, gives the serving path's span
tree with its request ids and counts, lands in the profile under the same
names, leaves served tokens unchanged, counts compiles per jitted entry,
and counts what a full channel drops."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.telemetry import CompileCounter, WallSpans, wall_spans
from repro.models.lm import build_model
from repro.serving import (DisaggConfig, DisaggServer, ServeRequest,
                           ServingEngine)
from repro.simcluster.hw import TPU_V5E

SHARED, SUFFIX = 32, 8


@pytest.fixture(scope="module")
def smollm():
    cfg = SMOKES["smollm-360m"]
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _waves(vocab):
    """A first request that registers a 32-token prefix, then two that
    reuse it and one that shares nothing."""
    rng = np.random.default_rng(3)
    shared = rng.integers(0, vocab, SHARED)

    def req(rid, t, toks):
        return ServeRequest(rid=rid, arrival=t, tokens=toks, max_new=3)

    first = [req(0, 0.0, np.concatenate([shared,
                                         rng.integers(0, vocab, SUFFIX)]))]
    second = [req(1, 1.0, np.concatenate([shared,
                                          rng.integers(0, vocab, SUFFIX)])),
              req(2, 1.0, np.concatenate([shared,
                                          rng.integers(0, vocab, 12)])),
              req(3, 1.0, rng.integers(0, vocab, 24))]
    return [first, second]


def _serve(smollm):
    """Serve both waves on a fresh server; {rid: tokens}, the results."""
    cfg, model, params = smollm
    srv = DisaggServer(model, params, cfg=DisaggConfig(
        n_prefill_units=2, n_pages=128, hw=TPU_V5E))
    results = []
    for wave in _waves(cfg.vocab):
        results += srv.serve(wave, decode_steps=2)
    while srv.decoder.n_active:
        srv.serve([], decode_steps=1)
    return {r.rid: list(srv.results[r.rid].tokens) for r in results}, results


@pytest.fixture(scope="module")
def traced(smollm, tmp_path_factory):
    """Both waves served under one profiler session: the channel's spans,
    the results, the untraced tokens and the profile's directory."""
    _serve(smollm)                        # compile outside the session
    plain, _ = _serve(smollm)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    wall_spans.clear()
    with jax.profiler.trace(log_dir):
        tokens, results = _serve(smollm)
    spans = list(wall_spans.spans)
    wall_spans.clear()
    return spans, tokens, results, plain, log_dir


def test_nothing_recorded_outside_a_profiler_session(smollm):
    wall_spans.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _serve(smollm)
    assert len(wall_spans.spans) == 0 and wall_spans.dropped == 0
    with wall_spans.span("repro.serve", requests=1) as span:
        assert not span.on


def test_span_tree_of_the_served_path(traced):
    spans, _, results, _, _ = traced
    by_sid = {s.sid: s for s in spans}
    serves = [s for s in spans if s.name == "repro.serve"]
    # two waves, then one call per decode step until no slot is live
    assert len(serves) >= 2 and serves[0].args == {"requests": 1}
    assert serves[1].args == {"requests": 3}
    assert all(s.parent == -1 for s in serves)
    for s in spans:
        assert s.start_ns <= s.end_ns
        if s.parent != -1:
            p = by_sid[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns

    def named(name):
        return [s for s in spans if s.name == name]

    for s in named("repro.runtime.run"):
        assert by_sid[s.parent].name == "repro.serve"
        assert set(s.args) <= {f"{k}{x}" for k in ("arr", "compute", "tick",
                                                   "dstep", "net")
                               for x in ("", "_ns")}
    assert sum(s.args.get("arr", 0) for s in named("repro.runtime.run")) == 4
    for name in ("repro.kv.match", "repro.kv.gather", "repro.prefill",
                 "repro.kv.register", "repro.decode.admit"):
        got = named(name)
        assert sorted(s.rid for s in got) == [0, 1, 2, 3], name
        assert all(by_sid[s.parent].name == "repro.runtime.run"
                   for s in got), name
    for name in ("repro.decode.launch", "repro.decode.wait",
                 "repro.decode.slots"):
        got = named(name)
        assert got and all(by_sid[s.parent].name == "repro.serve"
                           for s in got), name
    assert [s.args["live"] for s in named("repro.decode.launch")] == \
        [s.args["live"] for s in named("repro.decode.slots")]

    reused = {r.rid: r.reused_tokens for r in results}
    assert reused[1] == reused[2] == SHARED and reused[3] == 0
    for s in named("repro.prefill"):
        assert s.args["reused"] == reused[s.rid]
        n = {0: SHARED + SUFFIX, 1: SHARED + SUFFIX, 2: SHARED + 12,
             3: 24}[s.rid]
        assert s.args["computed"] == n - reused[s.rid]
    for s in named("repro.kv.gather"):
        assert s.args["reused"] == reused[s.rid]
    assert {s.rid: s.args["matched"] for s in named("repro.kv.match")} == \
        {0: 0, 1: SHARED, 2: SHARED, 3: 0}
    for s in named("repro.kv.register"):
        assert s.args["pool_full"] == 0 and s.args["pages"] >= 1
    # the second wave's three requests are live together, in three slots
    assert len({s.args["slot"] for s in named("repro.decode.admit")
                if s.rid}) == 3


def test_spans_land_in_the_profile_under_their_names(traced):
    from jax.profiler import ProfileData

    spans, _, _, _, log_dir = traced
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    host.setdefault(e.name, []).append(
                        int(e.end_ns) - int(e.start_ns))
    mine = {}
    for s in spans:
        mine.setdefault(s.name, []).append(s.duration_ns)
    assert set(host) == set(mine)
    for name, durs in mine.items():
        assert len(host[name]) == len(durs), name
        for a, b in zip(sorted(host[name]), sorted(durs)):
            assert abs(a - b) < 1_000_000, name


def test_served_tokens_equal_with_the_session_on_and_off(traced):
    _, tokens, _, plain, _ = traced
    assert tokens == plain and all(len(t) == 3 for t in tokens.values())


def test_compiles_counted_per_jitted_entry(smollm):
    cfg, model, params = smollm
    eng = ServingEngine(model, params)
    rng = np.random.default_rng(5)

    def full():
        return wall_spans.compiles.entry("prefill_full").compiles

    n0 = full()
    eng.prefill(rng.integers(0, cfg.vocab, 21))
    assert full() == n0 + 1
    eng.prefill(rng.integers(0, cfg.vocab, 21))     # same length: no compile
    assert full() == n0 + 1
    eng.prefill(rng.integers(0, cfg.vocab, 22))
    assert full() == n0 + 2
    total = wall_spans.compiles.total()
    assert total.compiles >= full() and total.seconds > 0


def test_compile_counter_gives_a_cache_hit_to_the_entry_it_served():
    c = CompileCounter()
    c.compiled("jit(prefill_suffix)", 2.0)
    c.cache_hit()
    c.compiled("jit(decode_step)", 0.5)
    c.compiled("jit(decode_step)", 0.25)
    assert c.entry("prefill_suffix").cache_hits == 0
    d = c.entry("decode_step")
    assert (d.compiles, d.cache_hits, d.seconds) == (2, 1, 0.75)
    assert c.entry("prefill_full").compiles == 0
    assert c.total().compiles == 3


def test_full_channel_counts_its_drops():
    ch = WallSpans(limit=3)
    ch.install(lambda: True, None)
    for i in range(5):
        with ch.span("repro.serve", requests=i) as outer:
            with ch.span("repro.kv.match", rid=i):
                pass
    assert ch.dropped == 7 and len(ch.spans) == 3
    # the newest are kept, and a child still names its parent
    assert [s.name for s in ch.spans] == ["repro.kv.match", "repro.serve",
                                          "repro.kv.match"]
    assert ch.spans[-1].parent == outer.sid
    ch.clear()
    assert len(ch.spans) == 0 and ch.dropped == 0


def test_probe_is_inert_while_not_recording():
    ch = WallSpans()
    with ch.span("repro.decode.slots", live=3) as span:
        span.set(live=4)
        span.add("arr", 1)
    assert not span.on and len(ch.spans) == 0 and ch.dropped == 0
