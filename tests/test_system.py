"""End-to-end system behaviour: the full stack wired together —
launcher-level serving with MFS over the virtual fabric, the paper's
headline ordering, and the dry-run cell planner covering the assigned
matrix."""
import numpy as np
import pytest

import jax

from repro.configs import ARCHS, SHAPES, SMOKES
from repro.core import make_policy
from repro.launch.serve import make_requests, run as serve_run
from repro.launch.specs import SKIP_REASONS, input_specs, plan_cells
from repro.simcluster.hw import TPU_V5E


def test_assigned_matrix_is_complete():
    """10 archs x 4 shapes = 40 cells; 8 documented long_500k skips."""
    cells = plan_cells()
    assert len(cells) == 40
    assert len(ARCHS) == 10 and len(SHAPES) == 4
    skips = [c for c in cells if c.skip]
    assert len(skips) == 8
    assert all(c.shape.name == "long_500k" for c in skips)
    runnable = {(c.arch, c.shape.name) for c in cells if not c.skip}
    assert ("mamba2-1.3b", "long_500k") in runnable
    assert ("recurrentgemma-9b", "long_500k") in runnable


def test_input_specs_all_cells():
    """input_specs produces weak-type-correct stand-ins for every cell."""
    for cell in plan_cells():
        if cell.skip:
            continue
        spec = input_specs(cell.arch, cell.shape.name)
        assert spec, (cell.arch, cell.shape.name)
        for name, s in spec.items():
            assert isinstance(s, jax.ShapeDtypeStruct)
            assert all(d > 0 for d in s.shape), (name, s)
        cfg = ARCHS[cell.arch]
        if cell.shape.kind != "decode":
            if cfg.family == "vlm":
                assert "inputs_embeds" in spec     # stubbed patch frontend
            if cfg.family == "audio":
                assert "src_embeds" in spec        # stubbed frame frontend


@pytest.mark.slow
def test_serve_launcher_policies_end_to_end():
    summary = serve_run("smollm-360m", n_requests=6, rps=500.0,
                        policies=("mfs", "fs"), verbose=False, hw=TPU_V5E)
    assert set(summary) == {"mfs", "fs"}
    for s in summary.values():
        assert 0.0 <= s["slo_attainment"] <= 1.0
        assert s["reuse_fraction"] >= 0.0


def test_paper_headline_ordering_micro():
    """The one-line version of the paper: under the Table-1 contention,
    MFS meets every deadline; every stage-agnostic baseline misses some."""
    from repro.core import MFSScheduler, Stage
    from repro.netsim.toy import make_flow, run_toy
    reqs = {"A": (2.0, 9.0, 18.0), "B": (4.0, 6.0, 12.0), "C": (3.0, 0.0, 7.0)}

    def misses(policy_name):
        flows = {}
        for rid, (nm, (size, remain, dr)) in enumerate(reqs.items()):
            dl = dr - remain if policy_name == "mfs" else dr
            flows[nm] = make_flow(Stage.P2D, size=size, deadline=dl, rid=rid)
        pol = MFSScheduler() if policy_name == "mfs" \
            else make_policy(policy_name)
        finish = run_toy(list(flows.values()), pol)
        return sum(finish[f.fid] + reqs[nm][1] > reqs[nm][2] + 1e-6
                   for nm, f in flows.items())

    assert misses("mfs") == 0
    for base in ("fs", "sjf", "edf", "karuna"):
        assert misses(base) >= 1, base


def test_smoke_configs_match_families():
    for name, cfg in SMOKES.items():
        assert cfg.family == ARCHS[name].family, name
        assert cfg.n_layers <= ARCHS[name].n_layers
        assert cfg.vocab <= ARCHS[name].vocab
