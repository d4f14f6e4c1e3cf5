"""Unit + property tests for the paper's core: MLU/RLI urgency, the RMLQ
invariants (I1-I4), RED, and Algorithm 1."""
import math

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (BatchLoad, Flow, MLUConfig, RMLQ, Stage,
                        geometric_thresholds, inter_request_schedule, mlu,
                        mlu_level, new_flow_id, red_score, rli_level)
from repro.core.msflow import FlowState
from repro.core.red import partition_by_max_gap


def _flow(stage=Stage.P2D, deadline=1.0, size=100.0):
    return Flow(fid=new_flow_id(), rid=0, unit=0, stage=stage, size=size,
                src=0, dst=1, target_layer=0, n_layers=8, deadline=deadline)


# ------------------------------------------------------------------ urgency
def test_mlu_basic():
    # 100 bytes, 1s budget, 200 B/s clean link -> needs half the link
    assert mlu(100, 1.0, 200.0) == pytest.approx(0.5)
    # background load halves effective capacity -> needs all of it
    assert mlu(100, 1.0, 200.0, rho=0.5) == pytest.approx(1.0)
    assert mlu(0.0, 1.0, 200.0) == 0.0
    assert math.isinf(mlu(100, 0.0, 200.0))
    assert math.isinf(mlu(100, -1.0, 200.0))


def test_geometric_ladder():
    qs = geometric_thresholds(8, E=4.0, U=0.5)
    assert len(qs) == 7
    for a, b in zip(qs, qs[1:]):
        assert a / b == pytest.approx(4.0)      # constant ratio = minimal
    assert qs[0] == pytest.approx(0.125)        # U * E^-1


def test_mlu_level_bands():
    cfg = MLUConfig(K=8, E=4.0, U=0.5)
    assert mlu_level(0.9, cfg) == 1             # critical
    assert mlu_level(0.5, cfg) == 1
    assert mlu_level(0.2, cfg) == 2             # within [Q_1, U)
    assert mlu_level(1e-9, cfg) == cfg.K        # ample laxity
    # infeasible flows are NOT promoted (Black-Hole guard)
    assert mlu_level(1.5, cfg) == cfg.K
    assert mlu_level(math.inf, cfg) == cfg.K


@given(st.floats(min_value=1e-9, max_value=1.0),
       st.floats(min_value=1e-9, max_value=0.999))
def test_mlu_level_monotone_in_urgency(v, smaller_frac):
    """More urgency never maps to a lower priority (level never increases)."""
    cfg = MLUConfig()
    lo = mlu_level(v * smaller_frac, cfg)
    hi = mlu_level(v, cfg)
    assert hi <= lo


def test_rli_level():
    cfg = MLUConfig(K=8)
    assert rli_level(0, cfg) == 2               # Stage-2: top of implicit band
    assert rli_level(1, cfg) == 3
    assert rli_level(100, cfg) == cfg.K         # capped at lowest queue (I4)
    assert rli_level(-3, cfg) == 2


# --------------------------------------------------------------------- RMLQ
def test_rmlq_monotone_promotion():
    q = RMLQ(MLUConfig(K=8))
    f = _flow()
    q.insert(f, 6)
    assert f.level == 6
    assert q.promote(f, 3) is True
    assert f.level == 3
    # I1: demotion requests are ignored
    assert q.promote(f, 7) is False
    assert f.level == 3


def test_rmlq_level1_reserved_for_explicit():
    q = RMLQ(MLUConfig(K=8))
    implicit = _flow(stage=Stage.COLLECTIVE, deadline=None)
    q.insert(implicit, 1)
    assert implicit.level == 2                  # I3: clamped out of level 1
    q.promote(implicit, 1)
    assert implicit.level == 2
    explicit = _flow(stage=Stage.P2D, deadline=5.0)
    q.insert(explicit, 1)
    assert explicit.level == 1


def test_rmlq_scavenger_cycle():
    q = RMLQ(MLUConfig(K=8))
    f = _flow()
    q.insert(f, 4)
    q.demote_to_scavenger(f)
    assert f.level == q.K + 1
    assert f.state == FlowState.PRUNED
    q.readmit(f, 5)
    assert f.level == 5
    assert f.state == FlowState.ACTIVE


@settings(max_examples=200, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 10), st.booleans()),
                min_size=1, max_size=40))
def test_rmlq_invariants_random_ops(ops):
    """Random insert/promote sequences preserve I1 + I3 + I4."""
    cfg = MLUConfig(K=8)
    q = RMLQ(cfg)
    flows = []
    for level, explicit in ops:
        f = _flow(stage=Stage.P2D if explicit else Stage.KV_REUSE,
                  deadline=1.0 if explicit else None)
        q.insert(f, level)
        flows.append((f, f.level))
    for f, initial in flows:
        assert 1 <= f.level <= cfg.K
        if not f.explicit_deadline:
            assert f.level >= 2                 # I3
        q.promote(f, f.level - 3)
        assert f.level <= initial               # I1 over the whole history


# ---------------------------------------------------------------------- RED
def test_red_partition():
    tight, loose = partition_by_max_gap([1.0, 1.1, 5.0, 5.2])
    assert tight == [1.0, 1.1]
    assert loose == [5.0, 5.2]


def test_red_counters_piggyback():
    """One tight outlier among many loose peers must NOT hijack the batch."""
    outlier_batch = [1.0] + [10.0] * 9          # f = 0.1
    uniform_batch = [5.0] * 10
    red_outlier = red_score(outlier_batch)
    red_uniform = red_score(uniform_batch)
    # plain EDF would order outlier_batch (min 1.0) first; RED does not
    assert red_outlier > red_uniform
    assert red_outlier == pytest.approx(0.1 * 1.0 + 0.9 * 10.0)


def test_red_all_tight_degenerates_to_edf():
    assert red_score([3.0, 3.0, 3.0]) == 3.0
    assert red_score([2.0]) == 2.0


@given(st.lists(st.floats(min_value=0.0, max_value=1e6), min_size=1,
                max_size=30))
def test_red_bounded_by_batch_extremes(ds):
    r = red_score(ds)
    assert min(ds) - 1e-6 <= r <= max(ds) + 1e-6


# -------------------------------------------------------------- Algorithm 1
def _mk_batch(bid, loads, deadlines, compute=0.0):
    return BatchLoad(bid=bid,
                     request_loads={r: np.asarray(l, np.float64)
                                    for r, l in loads.items()},
                     deadlines=deadlines, compute_time=compute)


def test_alg1_feasible_batches_untouched():
    bw = np.array([100.0, 100.0])
    b1 = _mk_batch(1, {1: [10, 0], 2: [0, 10]}, {1: 1.0, 2: 1.0})
    b2 = _mk_batch(2, {3: [10, 10]}, {3: 2.0})
    out = inter_request_schedule([b1, b2], bw)
    assert out.order == [1, 2]
    assert out.pruned == []


def test_alg1_prunes_black_hole():
    """An infeasible heavy request is pruned so viable peers survive."""
    bw = np.array([100.0])
    # rid 1 alone needs 10s on the port; deadline is 1s -> doomed
    b = _mk_batch(1, {1: [1000.0], 2: [20.0]}, {1: 1.0, 2: 1.0})
    out = inter_request_schedule([b], bw)
    assert (1, 1) in out.pruned
    assert (1, 2) not in out.pruned
    assert out.finish_estimates[1] <= 1.0 + 1e-9


def test_alg1_respects_drop_budget():
    bw = np.array([1.0])
    b = _mk_batch(1, {r: [100.0] for r in range(10)},
                  {r: 0.1 for r in range(10)})
    out = inter_request_schedule([b], bw, drop_budget=3)
    assert len(out.pruned) == 3


def test_alg1_order_is_red_order():
    bw = np.array([1e9])
    tightish = _mk_batch(1, {1: [1.0]}, {1: 5.0})
    urgent = _mk_batch(2, {2: [1.0]}, {2: 1.0})
    out = inter_request_schedule([tightish, urgent], bw)
    assert out.order == [2, 1]


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 5), st.integers(1, 4),
       st.floats(min_value=0.5, max_value=50.0))
def test_alg1_admitted_set_is_feasible(n_batches, n_req, deadline):
    """Property: after pruning, every batch's worst-case finish estimate
    meets its loose-min deadline (or the drop budget was exhausted)."""
    rng = np.random.default_rng(42)
    bw = np.array([10.0, 10.0])
    batches = []
    for b in range(n_batches):
        loads = {b * 10 + r: rng.uniform(0, 30, size=2) for r in range(n_req)}
        dls = {b * 10 + r: deadline * (1 + 0.1 * r) for r in range(n_req)}
        batches.append(_mk_batch(b, loads, dls))
    out = inter_request_schedule(batches, bw, drop_budget=10**9)
    for b in batches:
        remaining = [r for r in b.request_loads if (b.bid, r) not in out.pruned]
        if remaining:
            assert out.finish_estimates[b.bid] <= b.loose_min + 1e-6
