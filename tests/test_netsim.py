"""Fluid network model properties: strict priority, max-min fairness,
rate caps, conservation; event queue determinism."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import Stage, new_flow_id
from repro.core.msflow import Flow
from repro.netsim.events import EventQueue
from repro.netsim.fluid import FluidNet
from repro.netsim.topology import FatTree, SingleToR
from repro.netsim.toy import OneLink


def _flow(src=0, dst=1, size=100.0, key=(0,), cap=None, stage=Stage.P2D):
    f = Flow(fid=new_flow_id(), rid=0, unit=0, stage=stage, size=size,
             src=src, dst=dst, target_layer=0, n_layers=4, deadline=None)
    f.priority_key = key
    f.rate_cap = cap
    return f


def test_strict_priority_preempts():
    net = FluidNet(OneLink(1.0))
    hi = _flow(key=(0,))
    lo = _flow(key=(1,))
    net.add(hi); net.add(lo)
    net.reallocate()
    assert hi.rate == pytest.approx(1.0)
    assert lo.rate == pytest.approx(0.0)


def test_maxmin_within_group():
    net = FluidNet(OneLink(1.0))
    flows = [_flow(key=(0,)) for _ in range(4)]
    for f in flows:
        net.add(f)
    net.reallocate()
    for f in flows:
        assert f.rate == pytest.approx(0.25)


def test_rate_cap_respected_and_leftover_shared():
    net = FluidNet(OneLink(1.0))
    capped = _flow(key=(0,), cap=0.2)
    other = _flow(key=(0,))
    net.add(capped); net.add(other)
    net.reallocate()
    assert capped.rate == pytest.approx(0.2)
    assert other.rate == pytest.approx(0.8)


def test_completion_times_exact():
    net = FluidNet(OneLink(2.0))
    f = _flow(size=10.0, key=(0,))
    net.add(f)
    net.reallocate()
    nxt = net.next_completion()
    assert nxt[0] == pytest.approx(5.0)
    done = net.advance(5.0)
    assert done == [f]
    assert f.finished == pytest.approx(5.0)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.floats(0.1, 50.0)),
                min_size=1, max_size=12))
def test_conservation_no_link_oversubscribed(flows_spec):
    """Property: allocations never exceed any link capacity and every flow
    with a clear path makes progress."""
    topo = SingleToR(4, nic_bw=1.0, gpus_per_server=2, scaleup_bw=2.0)
    net = FluidNet(topo)
    flows = []
    for prio, size in flows_spec:
        f = _flow(src=np.random.randint(0, 4), dst=np.random.randint(0, 4),
                  size=size, key=(prio,))
        flows.append(f)
        net.add(f)
    net.reallocate()
    usage = {}
    for f in flows:
        for lid in net.routes[f.fid]:
            usage[lid] = usage.get(lid, 0.0) + f.rate
    for lid, u in usage.items():
        assert u <= topo.capacity[lid] + 1e-6
    # top-priority group always gets positive aggregate rate
    top = min(tuple(f.priority_key) for f in flows)
    assert sum(f.rate for f in flows if tuple(f.priority_key) == top) > 0


def test_fat_tree_ecmp_routes_consistent():
    topo = FatTree(racks=2, hosts_per_rack=4, nic_bw=1.0,
                   gpus_per_server=2, scaleup_bw=4.0)
    r1 = topo.route(0, 7, fid=42)
    r2 = topo.route(0, 7, fid=42)
    assert r1 == r2                              # per-flow deterministic
    assert len(r1) == 4                          # host-leaf-spine-leaf-host
    same_rack = topo.route(0, 3, fid=1)
    assert len(same_rack) == 2
    same_server = topo.route(0, 1, fid=1)
    assert len(same_server) == 2                 # scale-up fabric


def test_victim_unit_ingress_contention():
    """Many senders -> one victim endpoint: its downlink is the bottleneck
    (§2.2 inter-request contention)."""
    topo = SingleToR(4, nic_bw=1.0, gpus_per_server=1)
    net = FluidNet(topo)
    flows = [_flow(src=s, dst=0, size=10.0, key=(0,)) for s in (1, 2, 3)]
    for f in flows:
        net.add(f)
    net.reallocate()
    for f in flows:
        assert f.rate == pytest.approx(1.0 / 3.0)


def test_remove_purges_link_accounting():
    """Regression: cancelling a flow (e.g. pruned Stage-1 recompute) must
    release its rate from the link accounting immediately — otherwise
    ``bottleneck`` / ``bottleneck_protected`` rho stays inflated until the
    next reallocation."""
    net = FluidNet(OneLink(1.0))
    a = _flow(key=(0,))
    b = _flow(key=(0,))
    probe = _flow(key=(1,))
    for f in (a, b, probe):
        net.add(f)
    net.reallocate()
    assert a.rate == pytest.approx(0.5)
    net.remove(a)                      # cancelled, NOT followed by reallocate
    assert a.rate == 0.0
    _, rho = net.bottleneck(probe)
    assert rho == pytest.approx(0.5)   # only b's rate remains
    _, rho_p = net.bottleneck_protected(probe, lambda f: True)
    assert rho_p == pytest.approx(0.5)
    assert net._link_rate[0] == pytest.approx(0.5)


def test_completed_flows_release_bandwidth_accounting():
    """Flows finished by ``advance`` stop counting toward rho as well."""
    net = FluidNet(OneLink(1.0))
    small = _flow(size=1.0, key=(0,))
    big = _flow(size=100.0, key=(0,))
    probe = _flow(key=(1,))
    for f in (small, big, probe):
        net.add(f)
    net.reallocate()
    done = net.advance(2.0)            # small (1.0 bytes at 0.5) finishes
    assert done == [small]
    _, rho = net.bottleneck(probe)
    assert rho == pytest.approx(0.5)


def _random_churn(seed, incremental, n_flows=60, n_events=120):
    """Drive one FluidNet through a random add/remove/rekey/recap sequence;
    returns the rate vector after every reallocation."""
    rng = np.random.default_rng(seed)
    topo = FatTree(racks=2, hosts_per_rack=4, nic_bw=1.0,
                   gpus_per_server=2, scaleup_bw=4.0)
    net = FluidNet(topo, incremental=incremental)
    flows = []
    fid = 0
    def mk():
        nonlocal fid
        fid += 1
        f = _flow(src=int(rng.integers(0, topo.n_nodes)),
                  dst=int(rng.integers(0, topo.n_nodes)),
                  size=float(rng.uniform(1, 50)),
                  key=(int(rng.integers(0, 4)),),
                  cap=float(rng.uniform(0.05, 0.5))
                  if rng.uniform() < 0.3 else None)
        f.fid = 10_000 * (seed + 1) + fid       # deterministic across modes
        return f
    out = []
    for _ in range(n_flows):
        f = mk(); flows.append(f); net.add(f)
    for _ in range(n_events):
        op = rng.integers(0, 4)
        if op == 0 or not flows:
            f = mk(); flows.append(f); net.add(f)
        elif op == 1:
            f = flows.pop(int(rng.integers(len(flows)))); net.remove(f)
        elif op == 2:
            f = flows[int(rng.integers(len(flows)))]
            f.priority_key = (int(rng.integers(0, 4)),)
        else:
            f = flows[int(rng.integers(len(flows)))]
            f.rate_cap = float(rng.uniform(0.05, 0.5)) \
                if rng.uniform() < 0.5 else None
        net.reallocate()
        out.append(sorted((f.fid, f.rate) for f in flows))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_matches_full(seed):
    """Dirty-group incremental reallocation must produce BIT-IDENTICAL rates
    to the from-scratch allocation under arbitrary churn (adds, removals,
    key changes, cap changes)."""
    inc = _random_churn(seed, incremental=True)
    full = _random_churn(seed, incremental=False)
    assert inc == full                 # exact float equality, every epoch


def _wide_group_churn(warmstart, n_flows=128, n_events=60):
    """One wide single-key group (vectorized fill) under per-event
    membership churn; returns rates after every reallocation."""
    rng = np.random.default_rng(7)
    topo = FatTree(racks=2, hosts_per_rack=4, nic_bw=1.0,
                   gpus_per_server=2, scaleup_bw=4.0)
    net = FluidNet(topo)
    net.warmstart = warmstart
    fid = [0]
    def mk():
        fid[0] += 1
        f = _flow(src=int(rng.integers(0, topo.n_nodes)),
                  dst=int(rng.integers(0, topo.n_nodes)),
                  size=float(rng.uniform(1, 50)), key=(0,),
                  cap=float(rng.uniform(0.05, 0.5))
                  if rng.uniform() < 0.2 else None)
        f.fid = 500_000 + fid[0]
        return f
    flows = [mk() for _ in range(n_flows)]
    for f in flows:
        net.add(f)
    net.reallocate()
    out = [sorted((f.fid, f.rate) for f in flows)]
    for _ in range(n_events):
        victim = flows.pop(int(rng.integers(len(flows))))
        net.remove(victim)
        nf = mk()
        flows.append(nf)
        net.add(nf)
        net.reallocate()
        out.append(sorted((f.fid, f.rate) for f in flows))
    return out, net.stats


def test_warmstart_matches_cold():
    """Warm-started within-group fills (patched incidence structure) must
    produce BIT-IDENTICAL rates to cold from-scratch builds, and must
    actually take the patch path under pure membership churn."""
    warm, wstats = _wide_group_churn(True)
    cold, cstats = _wide_group_churn(False)
    assert warm == cold                # exact float equality, every epoch
    assert wstats["vec_patches"] > 0
    assert cstats["vec_patches"] == 0


def test_incremental_skips_clean_groups():
    """A reallocation with nothing changed must re-fill nothing; churn in
    the lowest-priority group must not re-fill the more urgent groups."""
    topo = SingleToR(8, nic_bw=1.0, gpus_per_server=2, scaleup_bw=2.0)
    net = FluidNet(topo)
    hi = [_flow(src=0, dst=4, key=(0,)) for _ in range(3)]
    lo = [_flow(src=1, dst=5, key=(9,)) for _ in range(3)]   # disjoint NICs
    for f in hi + lo:
        net.add(f)
    net.reallocate()
    fills0 = net.stats["group_fills"]
    net.reallocate()                   # no change at all -> zero fills
    assert net.stats["group_fills"] == fills0
    extra = _flow(src=1, dst=5, key=(9,))
    net.add(extra)
    net.reallocate()                   # dirty: only the (9,) group
    assert net.stats["group_fills"] == fills0 + 1
    for f in hi:
        assert f.rate == pytest.approx(1.0 / 3.0)


def test_next_completion_heap_matches_scan():
    """The lazy-invalidation heap must return the same prediction as a
    linear scan across rate changes, removals and partial progress."""
    rng = np.random.default_rng(3)
    topo = SingleToR(4, nic_bw=1.0, gpus_per_server=2, scaleup_bw=2.0)
    net = FluidNet(topo)
    flows = [_flow(src=int(rng.integers(0, 4)), dst=int(rng.integers(0, 4)),
                   size=float(rng.uniform(5, 50)),
                   key=(int(rng.integers(0, 3)),)) for _ in range(12)]
    for f in flows:
        net.add(f)
    t = 0.0
    for step in range(40):
        if step % 7 == 3 and net.flows:
            victim = next(iter(net.flows.values()))
            net.remove(victim)
        for f in net.flows.values():
            if rng.uniform() < 0.2:
                f.priority_key = (int(rng.integers(0, 3)),)
        net.reallocate()
        nxt = net.next_completion()
        best = min(((net.now + max(f.remaining / f.rate, 1e-12), f.fid)
                    for f in net.flows.values() if f.rate > 0.0),
                   default=None)
        if best is None:
            assert nxt is None
            break
        assert nxt is not None
        assert nxt[0] == pytest.approx(best[0], rel=1e-9)
        t = min(best[0], t + 0.5)
        net.advance(t)


def test_class_rates_tag_shared_links():
    """Per-link flow-class breakdown: a shared downlink reports how much
    bandwidth P2D vs D2D is actually holding."""
    net = FluidNet(OneLink(1.0))
    p2d = _flow(key=(0,), stage=Stage.P2D)
    d2d = _flow(key=(0,), stage=Stage.D2D)
    net.add(p2d); net.add(d2d)
    net.reallocate()
    by_class = net.class_rates(0)
    assert by_class[Stage.P2D] == pytest.approx(0.5)
    assert by_class[Stage.D2D] == pytest.approx(0.5)
    agg = net.class_utilization()
    assert agg[Stage.D2D] == pytest.approx(0.5)
    assert net.class_utilization(lids=[99]) == {}


def test_event_queue_fifo_and_epoch():
    q = EventQueue()
    q.push(1.0, "a", None)
    q.push(1.0, "b", None)
    q.push(0.5, "c", None)
    assert q.pop()[1] == "c"
    assert q.pop()[1] == "a"                     # FIFO tie-break
    assert q.pop()[1] == "b"
    with pytest.raises(ValueError):
        q.push(0.1, "late", None)                # scheduling into the past
