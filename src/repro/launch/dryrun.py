"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run before anything else initialises jax: the first lines
pin the CPU backend with 512 placeholder host devices so ``jax.make_mesh``
can build the production meshes (and a machine with a TPU never hands its
chip to this compile-only run). Do NOT set these env vars anywhere global —
smoke tests and benches see 1 device.

Per cell this entrypoint records:
  * compile success,
  * ``compiled.memory_analysis()``  (per-device bytes — proves it fits),
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for the roofline),
  * collective bytes parsed from the partitioned HLO text, per collective
    kind (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — cost_analysis does not expose these,
  * analytic per-device input residency (params + caches + batch).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh both \
        [--arch qwen1.5-32b ...] [--shape train_4k ...] [--out experiments]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
# the package has imported jax already (so JAX_PLATFORMS is read); no
# backend has started yet, so the config still pins it
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from .mesh import make_production_mesh
from .specs import Cell, build_cell, plan_cells

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"=\s*(?P<lhs>[^=]*?)\s+(?P<op>all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-kind result bytes of every collective in the partitioned HLO.

    ``-done`` variants are skipped (their ``-start`` twin already counted).
    Returns {kind: {count, bytes}} plus a total.
    """
    out: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m is None or "-done(" in line:
            continue
        kind = m.group("op")
        lhs = m.group("lhs")
        b = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(lhs))
        out[kind]["count"] += 1
        out[kind]["bytes"] += float(b)
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def _spec_shards(sharding, shape) -> int:
    """Number of devices one leaf is split over (for residency math)."""
    try:
        spec = sharding.spec
        mesh_shape = dict(zip(sharding.mesh.axis_names, sharding.mesh.shape.values())) \
            if hasattr(sharding.mesh.shape, "values") else None
    except AttributeError:
        return 1
    n = 1
    mesh = sharding.mesh
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            n *= mesh.shape[a]
    return n


def analytic_input_bytes(args, shardings) -> float:
    """Exact per-device residency of the cell's inputs."""
    leaves_a = jax.tree.leaves(args)
    leaves_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    total = 0.0
    for a, s in zip(leaves_a, leaves_s):
        size = np.prod(a.shape) * a.dtype.itemsize if a.shape else a.dtype.itemsize
        total += size / _spec_shards(s, a.shape)
    return total


def run_cell(cell: Cell, mesh, save_hlo: Optional[str] = None,
             unroll: bool = False) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"arch": cell.arch, "shape": cell.shape.name,
                           "kind": cell.kind, "mesh": "x".join(
                               f"{mesh.shape[a]}{a}" for a in mesh.axis_names)}
    if cell.skip:
        rec["status"] = "skip"
        rec["reason"] = cell.skip
        return rec
    t0 = time.time()
    try:
        cell = build_cell(cell, mesh, unroll=unroll)
        with mesh:
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings)
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
        rec["status"] = "ok"
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["model_flops"] = cell.model_flops
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            rec["cost_analysis"] = {
                "flops": float(ca.get("flops", -1.0)),
                "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            }
        except Exception as e:              # pragma: no cover
            rec["cost_analysis"] = {"error": str(e)}
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:              # pragma: no cover
            rec["memory_analysis"] = {"error": str(e)}
        rec["input_bytes_per_device"] = analytic_input_bytes(
            cell.args, cell.in_shardings)
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo)
        rec["hlo_instructions"] = hlo.count("\n")
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--out", default="experiments")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan so cost_analysis / collective"
                         " counts are exact (roofline pass; slower compiles)")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json filename")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    for mesh_name, mesh in meshes:
        results: List[Dict[str, Any]] = []
        for cell in plan_cells(args.arch, args.shape):
            hlo_path = (os.path.join(
                args.out, f"hlo_{mesh_name}_{cell.arch}_{cell.shape.name}.txt")
                if args.save_hlo else None)
            rec = run_cell(cell, mesh, save_hlo=hlo_path, unroll=args.unroll)
            results.append(rec)
            status = rec["status"]
            extra = ""
            if status == "ok":
                ma = rec.get("memory_analysis", {})
                arg_gb = ma.get("argument_size_in_bytes", 0) / 1e9
                col_gb = rec["collectives"]["total_bytes"] / 1e9
                extra = (f"args={arg_gb:.2f}GB/dev "
                         f"coll={col_gb:.3f}GB "
                         f"compile={rec['compile_s']}s")
            elif status == "fail":
                extra = rec["error"][:120]
            else:
                extra = rec["reason"][:60]
            print(f"[{mesh_name}] {cell.arch:22s} {cell.shape.name:12s} "
                  f"{status:4s} {extra}", flush=True)
        path = os.path.join(args.out, f"dryrun_{mesh_name}{args.tag}.json")
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
        ok = sum(r["status"] == "ok" for r in results)
        skip = sum(r["status"] == "skip" for r in results)
        fail = sum(r["status"] == "fail" for r in results)
        print(f"[{mesh_name}] done: {ok} ok / {skip} skip / {fail} fail "
              f"-> {path}", flush=True)


if __name__ == "__main__":
    main()
