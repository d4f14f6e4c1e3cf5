"""A new cell or metric is found by name: one file added, none edited."""
import json
import shutil

import pytest

from chipbench.spec import HERE, ROOT, load_cell


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_added_workload_and_metric_are_picked_up(checkout):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    mix = json.loads((checkout / "chipbench" / "workloads" /
                      "smollm-360m.code-reuse.json").read_text())
    mix["arrival"] = {"process": "gamma", "cv": 2.0}
    (checkout / "chipbench" / "workloads" / "smollm-360m.added.json").write_text(
        json.dumps(mix))
    (checkout / "chipbench" / "metrics" / "added_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["workloads"].append({"name": "smollm-360m.added",
                               "config": "smollm-360m",
                               "traffic": "smollm-360m.added", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "added_metric", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "test", "moves": "ttft_p95_ms",
                               "workloads": ["smollm-360m.added"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("smollm-360m.added", checkout)
    assert cell.traffic["arrival"]["cv"] == 2.0
    assert cell.config["name"] == "smollm-360m"
    added = [m for m in cell.per_layer if m.name == "added_metric"]
    assert added and added[0].read(None) == 42.0
    # the metric is for the added cell only
    other = load_cell("smollm-360m.code-reuse", checkout)
    assert "added_metric" not in [m.name for m in other.per_layer]


def test_every_named_file_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        names = {m.name for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names
        assert len(cell.end_to_end) >= 2 and cell.per_layer


TOY_FAMILY = '''"""A toy family: shapes as the file gives them; the
reference ranks tokens by id, highest first, and its control lowest
first."""
import numpy as np

def dims(conf):
    return {k: conf[k] for k in ("model_type", "vocab", "max_len",
                                 "n_layers", "n_heads", "n_kv", "head_dim",
                                 "d_model")}

def arch_config(conf):
    return {"family": "toyfam", "name": conf["name"]}

def logits(m, seed, seqs, rows, fp8=False):
    ramp = np.arange(m["vocab"], dtype=np.float32)
    return [np.tile(-ramp if fp8 else ramp, (len(r), 1)) for r in rows]

def matmul_per_token(m):
    return 1234.0
'''

TOY_CONF = {"name": "toy", "source": "test", "model_type": "toyfam",
            "vocab": 16, "max_len": 64, "n_layers": 2, "n_heads": 2,
            "n_kv": 1, "head_dim": 4, "d_model": 8}

TOY_RUN = '''import json
import numpy as np
import chipbench
from chipbench import check, flops, model
from chipbench.spec import load_cell
cell = load_cell("toy.cell")
m = model.dims(cell.config)
# served tokens 15 (the reference's best) and 3 (12 below it)
s = check.draw({0: (np.arange(5, dtype=np.int32), [15, 3])}, 7, 100, 1)
print(json.dumps({"package": chipbench.__file__, "dims": m,
                  "arch": model.arch_config(cell.config),
                  "matmul": flops.matmul_per_token(m),
                  "decode": flops.decode(m, 3),
                  "served_gap": check.served_gap(m, 7, s),
                  "control_gap": check.control_gap(m, 7, s)}))
'''


def _hashes(root):
    import hashlib
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_added_family_is_picked_up(checkout):
    """A configuration of a new ``model_type`` brings its family module,
    configuration, workload and ``BENCHMARK.json`` entries; shapes, the
    program's config, the reference check and the FLOP count all reach the
    family, and no file of the benchmark that was there changes."""
    import os
    import subprocess
    import sys

    from chipbench import flops
    bench_dir = checkout / "chipbench"
    before = _hashes(bench_dir)
    (bench_dir / "families" / "toyfam.py").write_text(TOY_FAMILY)
    conf = TOY_CONF
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(conf))
    (bench_dir / "workloads" / "toy.cell.json").write_text(
        (bench_dir / "workloads" / "smollm-360m.code-reuse.json").read_text())
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "chipbench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.cell", "config": "toy",
                               "traffic": "toy.cell", "chips": 1,
                               "why": "test"})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, PYTHONPATH=str(checkout),
               PYTHONDONTWRITEBYTECODE="1", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", TOY_RUN], cwd=checkout,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["package"].startswith(str(bench_dir))
    m = out["dims"]
    assert m == {k: conf[k] for k in m} and m["model_type"] == "toyfam"
    assert out["arch"] == {"family": "toyfam", "name": "toy"}
    assert out["matmul"] == 1234.0
    assert out["decode"] == (1234.0 + 2 * flops.attention(m, 1, 3)
                             + flops.logits(m))
    assert out["served_gap"] == 12.0 and out["control_gap"] == 15.0
    assert {p: h for p, h in _hashes(bench_dir).items() if p in before} \
        == before


def test_unknown_model_type_is_refused_with_the_families():
    from chipbench import model
    conf = json.loads((HERE / "configs" / "smollm-360m.json").read_text())
    with pytest.raises(ValueError,
                       match=r"'nosuch'.*families present: \[.*'llama'"):
        model.dims(dict(conf, model_type="nosuch"))


def test_smollm_dims_are_those_of_the_dense_family():
    from chipbench import model
    conf = json.loads((HERE / "configs" / "smollm-360m.json").read_text())
    assert model.dims(conf) == {
        "model_type": "llama", "d_model": 960, "n_layers": 32,
        "n_heads": 15, "n_kv": 5, "head_dim": 64, "d_ff": 2560,
        "vocab": 49152, "tied": True, "norm_eps": 1e-05,
        "rope_theta": 10000.0, "max_len": 2048}
    assert model.arch_config(conf).family == "dense"


def test_every_model_type_brings_its_family_and_tests():
    """Each ``model_type`` among the configurations has its family module
    and its own reference tests, ``tests/test_family_<model_type>.py``."""
    types = {json.loads(p.read_text())["model_type"]
             for p in (HERE / "configs").glob("*.json")}
    missing = [str(f.relative_to(HERE)) for t in sorted(types)
               for f in (HERE / "families" / f"{t}.py",
                         HERE / "tests" / f"test_family_{t}.py")
               if not f.is_file()]
    assert types and not missing, missing


TOY_TEST = '''"""The toy family's reference: tokens ranked by id."""
import json

import numpy as np

from chipbench import model
from chipbench.spec import HERE


def test_reference_ranks_by_id_and_control_reverses():
    m = model.dims(json.loads((HERE / "configs" / "toy.json").read_text()))
    logits = model.family(m).logits
    seqs, rows = [np.arange(5, dtype=np.int32)], [np.array([3, 4])]
    assert (logits(m, 7, seqs, rows)[0].argmax(-1) == m["vocab"] - 1).all()
    assert (logits(m, 7, seqs, rows, fp8=True)[0].argmax(-1) == 0).all()
'''

GUARD = ("chipbench/tests/test_discovery.py::"
         "test_added_family_brings_its_own_reference_tests")


def _pytest(root, *args):
    """A child pytest over the checkout at ``root`` (the guard left out, so
    that it does not run itself)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(ROOT / "src")]))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "--rootdir", str(root), "--deselect", GUARD, *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def test_added_family_brings_its_own_reference_tests(tmp_path):
    """A family, its configuration and its ``test_family_<model_type>.py``
    come as files only. The benchmark's test files that loop over every
    configuration pass with them, and so does the family's own file: its
    reference is judged there, and no other family's file takes a case of
    it. No file that was there changes. Without its test file the family
    is refused."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(bench_dir)
    (bench_dir / "families" / "toyfam.py").write_text(TOY_FAMILY)
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(TOY_CONF))
    toy_test = bench_dir / "tests" / "test_family_toyfam.py"
    toy_test.write_text(TOY_TEST)

    # the shared files that loop over every configuration glob configs/;
    # each other family's file is collected, not run: its cases are its own
    tests = sorted((bench_dir / "tests").glob("test_*.py"))
    shared = [str(p.relative_to(tmp_path)) for p in tests
              if not p.name.startswith("test_family_")
              and '"configs").glob(' in p.read_text()]
    families = [str(p.relative_to(tmp_path)) for p in tests
                if p.name.startswith("test_family_") and p != toy_test]
    p = _pytest(tmp_path, *shared, str(toy_test.relative_to(tmp_path)))
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert ("test_family_toyfam.py::"
            "test_reference_ranks_by_id_and_control_reverses PASSED"
            in p.stdout)
    p = _pytest(tmp_path, "--collect-only", *families)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "[toy]" not in p.stdout, p.stdout
    assert {p: h for p, h in _hashes(bench_dir).items() if p in before} \
        == before

    toy_test.unlink()
    p = _pytest(tmp_path, "chipbench/tests/test_discovery.py::"
                "test_every_model_type_brings_its_family_and_tests")
    assert p.returncode == 1, p.stdout[-4000:]
    assert "tests/test_family_toyfam.py" in p.stdout
