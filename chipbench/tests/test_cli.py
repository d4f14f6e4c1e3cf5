"""Off the chip the benchmark prints no result and exits non-zero."""
import os
import shutil
import subprocess
import sys

from chipbench.spec import HERE, ROOT

CMD = ["--workload", "smollm-360m.code-reuse", "--seed", "2147483659",
       "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + CMD, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT, "chipbench/run.py")
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_checkout_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "chipbench/run.py")
    assert p.returncode != 0
    assert "metrics" not in p.stdout
