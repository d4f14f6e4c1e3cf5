"""chip_smoke.py off the chip: its serve phase passes its own checks at
SMOKE size with the kernels in interpret mode, and its entry point refuses
to run (non-zero exit, no result line) without a TPU backend."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke                                            # noqa: E402
from repro.configs import SMOKES                             # noqa: E402
from repro.simcluster.hw import TPU_V5E                      # noqa: E402


def test_serve_phase_passes_at_smoke_size(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    lines = []
    failed = chip_smoke.serve_phase(
        SMOKES[chip_smoke.ARCH], prefix_len=64, suffix_len=16, max_new=4,
        hw=TPU_V5E, on_chip=False, log=lines.append)
    assert failed == [], "\n".join(lines)
    assert any("reuse vs recompute" in ln for ln in lines)
    # the compiles line reads the program's per-entry counter
    line = chip_smoke.compile_line()
    assert line.startswith("compiles: ")
    by_entry = dict(x.rsplit(" ", 1) for x in
                    line.split("by entry: ")[1].split(", "))
    assert int(by_entry["prefill_full"]) >= 1
    assert int(by_entry["decode_step"]) >= 1


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu_backend(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
