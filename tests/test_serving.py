"""Serving substrate tests: paged KV store + prefix index, continuous
batching decode, and the end-to-end disaggregated orchestrator."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import SMOKES
from repro.core import make_policy
from repro.models.lm import build_model
from repro.serving import (DecodeBatch, DisaggConfig, DisaggServer,
                           PagedStore, PrefixIndex, ServeRequest,
                           ServingEngine, cache_has_state)
from repro.simcluster.hw import TPU_V5E

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smollm():
    cfg = SMOKES["smollm-360m"]
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


# ------------------------------------------------------------------ paged KV
def test_paged_roundtrip(smollm):
    cfg, model, params = smollm
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, 24)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks})
    store = PagedStore(page_size=8, n_pages=32)
    pages = store.put(cache, 24)
    assert len(pages) == 3
    got = store.gather(pages, 24)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_refcounting(smollm):
    cfg, model, params = smollm
    rng = np.random.default_rng(1)
    store = PagedStore(page_size=8, n_pages=8)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, 16)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks})
    pages = store.put(cache, 16)
    free0 = store.alloc.n_free
    store.retain(pages)
    store.release(pages)
    assert store.alloc.n_free == free0         # still held by first ref
    store.release(pages)
    assert store.alloc.n_free == free0 + len(pages)


def test_prefix_index_page_aligned_match(smollm):
    cfg, model, params = smollm
    rng = np.random.default_rng(2)
    store = PagedStore(page_size=8, n_pages=64)
    index = PrefixIndex(store)
    base = rng.integers(0, cfg.vocab, size=(24,))
    _, cache = model.prefill(
        params, {"tokens": jnp.asarray(base[None], jnp.int32)})
    pages = store.put(cache, 24)
    index.insert_paged(base, pages, owner_unit=0, per_token_bytes=100.0)
    # same 24-token prefix, new suffix -> matches the full 24 (3 pages)
    query = np.concatenate([base, rng.integers(0, cfg.vocab, size=(10,))])
    e = index.match(query)
    assert e is not None and e.n_tokens == 24
    # diverges inside page 2 -> only the first 8-token page matches
    query2 = base.copy()
    query2[9] = (query2[9] + 1) % cfg.vocab
    e2 = index.match(query2)
    assert e2 is not None and e2.n_tokens == 8
    # completely different -> no match
    assert index.match(rng.integers(0, cfg.vocab, size=(24,))) is None


def test_snapshot_regime_for_ssm():
    cfg = SMOKES["mamba2-1.3b"]
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, size=(20,))
    _, cache = model.prefill(
        params, {"tokens": jnp.asarray(toks[None], jnp.int32)})
    assert cache_has_state(cache)
    store = PagedStore(page_size=8, n_pages=8)
    index = PrefixIndex(store)
    index.insert_snapshot(toks, cache, owner_unit=1)
    q = np.concatenate([toks, rng.integers(0, cfg.vocab, size=(5,))])
    e = index.match(q)
    assert e is not None and e.n_tokens == 20 and e.owner_unit == 1
    got = index.fetch(e)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- continuous batching
def _greedy_reference(model, params, prompt, n):
    """``n`` greedy tokens by teacher-forced full prefill (no decode cache)."""
    seq, want = list(prompt), []
    for _ in range(n):
        lg, _ = model.prefill(
            params, {"tokens": jnp.asarray(np.asarray(seq)[None], jnp.int32)})
        want.append(int(jnp.argmax(lg[0, -1])))
        seq.append(want[-1])
    return want


@pytest.mark.parametrize("arch", ["smollm-360m", "recurrentgemma-9b",
                                  "deepseek-moe-16b"])
@pytest.mark.slow
def test_decode_batch_matches_single_sequence(arch):
    """Slotted batched decode produces the same greedy tokens as prefilling
    the whole continuation (teacher-forced check)."""
    cfg = SMOKES[arch]
    model = build_model(cfg)
    params = model.init(KEY)
    eng = ServingEngine(model, params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=(n,)) for n in (12, 19)]
    db = DecodeBatch(model, params, capacity=64, max_slots=4)
    first, caches = {}, {}
    for rid, p in enumerate(prompts):
        first[rid], caches[rid], _ = eng.prefill(p)
        db.add(rid, caches[rid], len(p), first[rid], max_new=3)
    batched = {rid: [first[rid]] for rid in first}
    while db.n_active:
        for rid, t in db.step().items():
            batched[rid].append(t)
    for rid, p in enumerate(prompts):
        want = _greedy_reference(model, params, p, 3)
        assert batched[rid][:3] == want, (arch, rid, batched[rid], want)


def test_decode_batch_slot_recycling(smollm):
    cfg, model, params = smollm
    eng = ServingEngine(model, params)
    db = DecodeBatch(model, params, capacity=32, max_slots=2)
    rng = np.random.default_rng(5)
    for rid in range(4):                       # 4 requests through 2 slots
        p = rng.integers(0, cfg.vocab, size=(8 + rid,))
        t, c, _ = eng.prefill(p)
        db.add(rid, c, len(p), t, max_new=2)
        while db.n_active == db.max_slots:
            db.step()
    while db.n_active:
        db.step()
    assert len(db._free) == db.max_slots


def test_decode_batch_tokens_match_reference_across_slot_reuse(smollm):
    """Five requests through three slots, admitted as slots free up between
    steps, so that a retired slot is reused while the others are
    mid-sequence: each request's tokens are the per-sequence greedy
    reference's."""
    cfg, model, params = smollm
    eng = ServingEngine(model, params)
    db = DecodeBatch(model, params, capacity=32, max_slots=3)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, size=(n,))
               for n in (9, 13, 7, 11, 10)]
    max_new = [4, 2, 5, 3, 2]
    got, reused = {}, False
    waiting, used = list(range(len(prompts))), set()
    while waiting or db.n_active:
        while waiting and db.n_active < db.max_slots:
            rid = waiting.pop(0)
            t, c, _ = eng.prefill(prompts[rid])
            slot = db.add(rid, c, len(prompts[rid]), t, max_new=max_new[rid])
            got[rid] = [t]
            reused |= slot in used and db.n_active > 1
            used.add(slot)
        for rid, t in db.step().items():
            got[rid].append(t)
    assert reused
    for rid, p in enumerate(prompts):
        assert got[rid] == _greedy_reference(model, params, p,
                                             max_new[rid]), rid


def test_decode_step_uploads_explicitly_and_reads_once(smollm, monkeypatch):
    """After a warm-up step, a step runs with implicit host-to-device
    transfers disallowed, and reads the device exactly once whatever the
    number of live slots."""
    cfg, model, params = smollm
    eng = ServingEngine(model, params)
    db = DecodeBatch(model, params, capacity=32, max_slots=4)
    rng = np.random.default_rng(9)

    def admit(rid, max_new):
        p = rng.integers(0, cfg.vocab, size=(8,))
        t, c, _ = eng.prefill(p)
        db.add(rid, c, len(p), t, max_new=max_new)

    reads = []
    device_get = jax.device_get

    def counting_get(x):
        reads.append(1)
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    admit(0, max_new=8)
    db.step()                                  # warm-up: compiles the step
    reads.clear()
    db.step()
    assert db.n_active == 1 and len(reads) == 1
    admit(1, max_new=3)                        # retires on the second step
    admit(2, max_new=8)
    db.step()
    reads.clear()
    with jax.transfer_guard_host_to_device("disallow"):
        out = db.step()
    assert set(out) == {0, 1, 2} and len(reads) == 1
    assert sorted(s.rid for s in db.slots.values()) == [0, 2]


def test_decode_admit_and_step_compile_once(smollm):
    """After one admission and one step, a later admission (into the cache
    a step wrote) and step compile nothing: the warm-up before a serving
    window covers both."""
    from repro.core.telemetry import wall_spans
    cfg, model, params = smollm
    eng = ServingEngine(model, params)
    db = DecodeBatch(model, params, capacity=32, max_slots=3)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)) for _ in range(3)]
    for rid, p in enumerate(prompts):
        t, c, _ = eng.prefill(p)
        if rid == 1:
            before = wall_spans.compiles.total().compiles
        db.add(rid, c, len(p), t, max_new=4)
        db.step()
    assert wall_spans.compiles.total().compiles == before


# ------------------------------------------------------------- orchestrator
def test_disagg_server_end_to_end(smollm):
    cfg, model, params = smollm
    rng = np.random.default_rng(6)
    shared = rng.integers(0, cfg.vocab, size=(32,))
    reqs = []
    for i in range(6):
        if i % 2 == 0:
            toks = np.concatenate(
                [shared, rng.integers(0, cfg.vocab, size=(10,))])
        else:
            toks = rng.integers(0, cfg.vocab, size=(40,))
        reqs.append(ServeRequest(rid=i, arrival=i * 1e-4, tokens=toks,
                                 max_new=3))
    srv = DisaggServer(model, params,
                       cfg=DisaggConfig(n_prefill_units=2, n_pages=128,
                                        hw=TPU_V5E))
    res = srv.serve(reqs)
    assert len(res) == 6
    assert all(r.ttft > 0 for r in res)
    assert all(len(r.tokens) >= 1 for r in res)
    # prefix reuse kicked in for the later shared-prefix requests
    assert any(r.reused_tokens >= 32 for r in res)
    # determinism of the data plane: same tokens => same first token for the
    # two requests that share the full input... (rid0 vs rid2 share only the
    # prefix, so just check reuse didn't corrupt outputs: finite + in-vocab)
    assert all(0 <= t < cfg.vocab for r in res for t in r.tokens)


def test_disagg_reuse_is_exact(smollm):
    """A request served via Stage-1 reuse produces the same first token as
    the identical request served cold."""
    cfg, model, params = smollm
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, size=(32,))
    sfx = rng.integers(0, cfg.vocab, size=(8,))
    toks = np.concatenate([shared, sfx])
    cold = DisaggServer(model, params,
                        cfg=DisaggConfig(n_prefill_units=1, n_pages=64,
                                         hw=TPU_V5E))
    r_cold = cold.serve([ServeRequest(0, 0.0, toks, max_new=1)])[0]
    warm = DisaggServer(model, params,
                        cfg=DisaggConfig(n_prefill_units=1, n_pages=64,
                                         hw=TPU_V5E))
    warm.serve([ServeRequest(0, 0.0, np.concatenate(
        [shared, rng.integers(0, cfg.vocab, size=(6,))]), max_new=1)])
    r_warm = warm.serve([ServeRequest(1, 1.0, toks, max_new=1)])[0]
    assert r_warm.reused_tokens >= 32
    assert r_warm.first_token == r_cold.first_token


@pytest.mark.slow
def test_disagg_policies_all_run(smollm):
    cfg, model, params = smollm
    rng = np.random.default_rng(8)
    reqs = [ServeRequest(i, i * 1e-4,
                         rng.integers(0, cfg.vocab, size=(24,)), max_new=1)
            for i in range(4)]
    for pol in ("mfs", "fs", "sjf", "edf", "karuna"):
        srv = DisaggServer(model, params, policy=make_policy(pol),
                           cfg=DisaggConfig(n_prefill_units=2, hw=TPU_V5E))
        res = srv.serve(reqs)
        assert len(res) == 4


def test_gather_slice_stitches_to_full_gather(smollm):
    """Chunk-sliced materialisation (chunked prefill's data-plane mirror):
    concatenating token slices along the token axis must reproduce the
    monolithic gather exactly, including page-misaligned slice bounds."""
    cfg, model, params = smollm
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, 29)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks})
    store = PagedStore(page_size=8, n_pages=32)
    pages = store.put(cache, 29)
    full = store.gather(pages, 29)
    for bounds in ([0, 13, 29], [0, 8, 16, 29], [0, 29]):
        slices = [store.gather_slice(pages, a, b)
                  for a, b in zip(bounds, bounds[1:])]
        got = slices[0] if len(slices) == 1 else jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=2), *slices)
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        store.gather_slice(pages, 5, 5)


def test_chunked_disagg_reuse_is_exact(smollm):
    """Chunked prefill on the serve path: reuse results must stay exactly
    equal to a cold run — the sliced prefix materialisation feeds the real
    engine the same pages."""
    from repro.core.stages import ChunkSpec
    from repro.simcluster.hw import A100

    cfg, model, params = smollm
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab, size=(24,))
    suffix = rng.integers(0, cfg.vocab, size=(9,))
    full = np.concatenate([prefix, suffix])

    cold = DisaggServer(model, params, cfg=DisaggConfig(
        n_prefill_units=1, gpus_per_unit=1, layer_groups=2, hw=A100,
        n_pages=64, page_size=8))
    want = cold.serve([ServeRequest(rid=0, arrival=0.0, tokens=full,
                                    max_new=1)])[0]

    srv = DisaggServer(model, params, cfg=DisaggConfig(
        n_prefill_units=1, gpus_per_unit=1, layer_groups=2, hw=A100,
        n_pages=64, page_size=8,
        chunk=ChunkSpec(chunk_tokens=8)))
    res = srv.serve([
        ServeRequest(rid=0, arrival=0.0, tokens=prefix, max_new=1),
        ServeRequest(rid=1, arrival=0.05, tokens=full, max_new=1),
    ])
    assert res[1].reused_tokens == 24          # page-aligned prefix hit
    assert res[1].first_token == want.first_token


def test_disagg_reports_skipped_registration_and_decode(smollm):
    """A full page pool and full decode slots are reported per request,
    not swallowed."""
    cfg, model, params = smollm
    rng = np.random.default_rng(9)
    reqs = [ServeRequest(i, i * 1e-4, rng.integers(0, cfg.vocab, size=(40,)),
                         max_new=2) for i in range(3)]
    srv = DisaggServer(model, params, cfg=DisaggConfig(
        n_prefill_units=1, n_pages=4, page_size=16, decode_slots=2,
        hw=TPU_V5E))
    res = srv.serve(reqs)
    # 40 tokens take 3 pages and the index keeps the 2 full ones: after the
    # first request 2 of the 4 pages are free, too few for the next
    assert [r.prefix_registered for r in res] == [True, False, False]
    assert [r.decode_admitted for r in res] == [True, True, False]
    assert [len(r.tokens) for r in res] == [2, 2, 1]


def test_disagg_hw_comes_from_the_device(smollm):
    """Without an explicit HW the server prices its clock with the peak
    table of the chip it runs on; off a known chip that is an error."""
    from repro.simcluster.hw import hw_for_device
    cfg, model, params = smollm
    assert hw_for_device("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError):
        hw_for_device("TPU v99")
    with pytest.raises(KeyError):
        DisaggServer(model, params, cfg=DisaggConfig(n_prefill_units=1))
