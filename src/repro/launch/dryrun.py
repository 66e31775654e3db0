import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract the roofline inputs.

The two lines above MUST stay the first statements in this module (before
any jax-importing import): jax locks the device count at first init, and
this module — and ONLY this module — needs 512 host placeholder devices to
build the 2x16x16 multi-pod mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out artifacts/dryrun
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import registry
from ..configs.base import ArchConfig
from ..models import lm
from ..models import sharding as shd
from . import hlo_stats
from . import mesh as mesh_mod
from . import steps

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES.get(dt, 4)
    return total


def _group_size(line: str, world: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return world


def parse_collectives(hlo_text: str, world: int):
    """Per-device ICI byte accounting from the SPMD-partitioned HLO.

    Shapes in the partitioned module are per-device (local). Bytes moved
    per device, ring algorithms:
      all-gather        out_local × (n-1)/n   (received)
      reduce-scatter    out_local × (n-1)    (sent, = in×(n-1)/n)
      all-reduce        2 × out_local × (n-1)/n
      all-to-all        out_local × (n-1)/n
      collective-permute out_local
    """
    per_op = {k: {"count": 0, "bytes": 0.0, "out_bytes": 0} for k in
              COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\]"
                     r"(?:\{[^}]*\})?)\s+([a-z\-]+)", stripped)
        if not m:
            continue
        opname = m.group(2)
        base = opname.removesuffix("-start").removesuffix("-done")
        if base not in COLLECTIVES or opname.endswith("-done"):
            continue
        out_b = _shape_bytes(m.group(1))
        n = max(_group_size(stripped, world), 1)
        if base == "all-gather":
            moved = out_b * (n - 1) / n
        elif base == "reduce-scatter":
            moved = out_b * (n - 1)
        elif base == "all-reduce":
            moved = 2 * out_b * (n - 1) / n
        elif base == "all-to-all":
            moved = out_b * (n - 1) / n
        else:  # collective-permute
            moved = out_b
        per_op[base]["count"] += 1
        per_op[base]["bytes"] += moved
        per_op[base]["out_bytes"] += out_b
    total = sum(v["bytes"] for v in per_op.values())
    return per_op, total


def dryrun_cell(arch: str, shape_name: str, mesh_kind: str,
                save_hlo: str | None = None) -> dict:
    cfg = registry.get(arch)
    shape = registry.get_shape(cfg, shape_name)
    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    world = mesh.size
    t0 = time.time()
    with shd.mesh_context(mesh):
        fn, args = steps.jitted_cell(cfg, shape)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}
    hlo = compiled.as_text()
    per_op, coll_bytes = parse_collectives(hlo, world)
    trip_aware = hlo_stats.analyze(hlo, world)
    if save_hlo:
        pathlib.Path(save_hlo).write_text(hlo)
    # Analytic per-device parameter bytes (from shardings).
    psh = None
    with shd.mesh_context(mesh):
        psh = steps.param_shardings(cfg)
    pspecs = registry.params_specs(cfg)
    pbytes = 0
    for sh, p in zip(jax.tree.leaves(psh), jax.tree.leaves(pspecs)):
        shard_shape = sh.shard_shape(p.shape)
        n = 1
        for d in shard_shape:
            n *= d
        pbytes += n * p.dtype.itemsize
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "world": world,
        "kind": shape.kind,
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes accessed"),
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_info,
        "param_bytes_per_device": pbytes,
        "collectives_body_once": per_op,
        "collective_bytes_body_once": coll_bytes,
        # trip-count-aware per-device totals (launch/hlo_stats.py)
        "hlo_flops_per_device": trip_aware["flops"],
        "hlo_hbm_bytes_per_device": trip_aware["hbm_bytes"],
        "collective_bytes_per_device": trip_aware["collective_bytes"],
        "collectives": trip_aware["collectives"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "ok": True,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--meshes", default="pod,multipod")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch, shape in registry.runnable_cells():
            for mk in args.meshes.split(","):
                cells.append((arch, shape, mk))
        # smallest models first so progress accrues early
        cells.sort(key=lambda c: registry.get(c[0]).params_count())
        (outdir / "skipped.json").write_text(
            json.dumps([{"arch": a, "shape": s,
                         "reason": "full attention at 524k (O(L^2)); "
                                   "sub-quadratic archs only"}
                        for a, s in registry.skipped_cells()], indent=1))
    else:
        cells.append((args.arch, args.shape, args.mesh))

    n_ok = n_fail = n_skip = 0
    for arch, shape, mk in cells:
        tag = f"{arch}__{shape}__{mk}"
        path = outdir / f"{tag}.json"
        if path.exists() and args.all:
            prev = json.loads(path.read_text())
            if prev.get("ok"):
                n_skip += 1
                continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            rec = dryrun_cell(arch, shape, mk, save_hlo=args.save_hlo)
            n_ok += 1
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
            n_fail += 1
        path.write_text(json.dumps(rec, indent=1))
        status = "OK" if rec["ok"] else "FAIL"
        extra = ""
        if rec["ok"]:
            extra = (f" flops/dev={rec['hlo_flops_per_device']:.3g}"
                     f" coll_bytes/dev={rec['collective_bytes_per_device']:.3g}"
                     f" compile={rec['compile_s']}s")
        print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    print(f"[dryrun] done ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
