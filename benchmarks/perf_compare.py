"""§Perf harness — measure one (arch, shape, mesh) cell under a set of
baseline kill-switch env vars vs the optimized defaults.

Runs each configuration in a SUBPROCESS (several switches are read at
import time) and prints the roofline-relevant numbers side by side.

    PYTHONPATH=src python -m benchmarks.perf_compare \
        --arch minitron-8b --shape decode_32k --unroll \
        --baseline-env REPRO_BASELINE_EXPAND_KV=1
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

_CHILD = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import plan_cells, build_cell
from repro.launch.dryrun import collective_bytes

arch, shape, mesh_kind, unroll, out = sys.argv[1:6]
mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
cell = plan_cells([arch], [shape])[0]
cell = build_cell(cell, mesh, unroll=(unroll == "1"))
with mesh:
    compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings).lower(*cell.args).compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):
    ca = ca[0]
ma = compiled.memory_analysis()
rec = {
    "flops": float(ca.get("flops", -1.0)),
    "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
    "collective_bytes": collective_bytes(compiled.as_text())["total_bytes"],
    "temp_gb": ma.temp_size_in_bytes / 1e9,
    "args_gb": ma.argument_size_in_bytes / 1e9,
    "model_flops": cell.model_flops,
}
with open(out, "w") as f:
    json.dump(rec, f)
"""

PEAK, HBM, LINK = 197e12, 819e9, 50e9


def run_once(arch: str, shape: str, mesh: str, unroll: bool,
             extra_env: dict) -> dict:
    env = dict(os.environ)
    env.update(extra_env)
    env.pop("XLA_FLAGS", None)
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out = f.name
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, arch, shape, mesh,
         "1" if unroll else "0", out],
        env=env, capture_output=True, text=True, timeout=7200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    with open(out) as f:
        return json.load(f)


def terms(rec: dict) -> dict:
    return {
        "compute_s": rec["flops"] / PEAK,
        "memory_s": rec["bytes_accessed"] / HBM,
        "collective_s": rec["collective_bytes"] / LINK,
        "temp_gb": rec["temp_gb"],
        "args_gb": rec["args_gb"],
        "useful": rec["model_flops"] / max(rec["flops"] * 256, 1e-9),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--baseline-env", nargs="*", default=[])
    args = ap.parse_args()

    base_env = dict(kv.split("=", 1) for kv in args.baseline_env)
    base = terms(run_once(args.arch, args.shape, args.mesh, args.unroll,
                          base_env))
    opt = terms(run_once(args.arch, args.shape, args.mesh, args.unroll, {}))
    print(f"cell: {args.arch} x {args.shape} ({args.mesh} pod"
          f"{', unrolled' if args.unroll else ''})")
    print(f"{'metric':14s} {'baseline':>12s} {'optimized':>12s} {'delta':>8s}")
    for k in ("compute_s", "memory_s", "collective_s", "temp_gb", "args_gb",
              "useful"):
        b, o = base[k], opt[k]
        delta = (o - b) / b if b else float("inf")
        print(f"{k:14s} {b:12.4f} {o:12.4f} {delta:+8.1%}")


if __name__ == "__main__":
    main()
