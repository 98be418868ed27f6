import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on
512 placeholder host devices; record memory analysis, FLOPs/bytes and the
collective schedule for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
Cells are cached as JSON (one file per cell) and skipped when present —
the sweep is resumable.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from ..configs import ARCH_IDS, SHAPES, SHAPES_BY_NAME, get_config  # noqa: E402
from . import specs as specs_lib  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Sum result-shape bytes per collective op kind (wire-bytes proxy;
    ring factors applied in roofline.py)."""
    out: dict[str, int] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        if "-done(" in line:
            continue
        type_str, op = m.group(1), m.group(2)
        out[op] = out.get(op, 0) + _shape_bytes(type_str)
        counts[op] = counts.get(op, 0) + 1
    return out, counts


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool, mesh=None):
    cfg = get_config(arch_id)
    shape = SHAPES_BY_NAME[shape_name]
    ok, reason = specs_lib.cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
                "status": reason}
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args, in_sh, out_sh = specs_lib.build_cell(cfg, shape, mesh)
    jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    lowered = jfn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec = {
        "arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "kind": shape.kind,
        "n_devices": mesh.devices.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "microbatches": (specs_lib.choose_microbatches(cfg, shape, mesh)
                          if shape.kind == "train" else 1),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    try:
        mem = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                rec[f] = int(v)
    except Exception as e:  # CPU backend may not implement it
        rec["memory_analysis_error"] = str(e)
    try:
        cost = compiled.cost_analysis()
        rec["hlo_flops"] = float(cost.get("flops", -1))
        rec["hlo_bytes"] = float(cost.get("bytes accessed", -1))
        rec["cost_raw"] = {k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float)) and ("bytes" in k or k in ("flops", "transcendentals"))}
    except Exception as e:
        rec["cost_analysis_error"] = str(e)
    try:
        text = compiled.as_text()
        coll, counts = parse_collectives(text)
        rec["collective_bytes"] = coll
        rec["collective_counts"] = counts
        rec["hlo_lines"] = text.count("\n")
    except Exception as e:
        rec["collective_error"] = str(e)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = []
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    mesh_cache = {}
    for arch_id, shape_name, mp in cells:
        mesh_dir = "multipod_2x16x16" if mp else "pod_16x16"
        out_dir = os.path.join(args.out, mesh_dir, arch_id)
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{shape_name}.json")
        if os.path.exists(path) and not args.force:
            print(f"[skip] {mesh_dir}/{arch_id}/{shape_name} (cached)")
            continue
        if mp not in mesh_cache:
            mesh_cache[mp] = make_production_mesh(multi_pod=mp)
        print(f"[run ] {mesh_dir}/{arch_id}/{shape_name} ...", flush=True)
        try:
            rec = run_cell(arch_id, shape_name, multi_pod=mp, mesh=mesh_cache[mp])
        except Exception as e:
            rec = {"arch": arch_id, "shape": shape_name, "multi_pod": mp,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"       -> {rec.get('status')} "
              f"(lower {rec.get('lower_s', '-')}s, compile {rec.get('compile_s', '-')}s, "
              f"flops {rec.get('hlo_flops', '-')})", flush=True)


if __name__ == "__main__":
    main()
