"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this records, into results/dryrun/<arch>__<shape>__<mesh>.json:
  * compiled cost_analysis (HLO flops / bytes accessed, per device),
  * memory_analysis (argument/output/temp bytes per device — proves fit),
  * the collective schedule: per-op wire bytes parsed from the partitioned
    HLO (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute),
  * the three roofline terms (compute / memory / collective, seconds) and
    the dominant bottleneck.

Resumable: existing cell files are skipped unless --force.
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from ..configs.base import SHAPES, shape_applicable
from ..models import registry
from . import steps as steps_lib
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh, pin_host_platform

# TPU v5e-class hardware constants (per chip) — source of truth lives in
# sweep.py (importable without jax); re-exported here for the compiled path
from .sweep import HBM_BW, ICI_BW, PEAK_FLOPS  # noqa: E402

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLL_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_stats(hlo_text: str, n_devices: int) -> dict:
    """Per-device wire bytes for each collective op in the partitioned HLO."""
    per_op: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("shape"))
        n = max(_group_size(line, n_devices), 1)
        frac = (n - 1) / n
        if op == "all-reduce":
            wire = 2 * size * frac
        elif op == "collective-permute":
            wire = size
        else:  # all-gather / reduce-scatter / all-to-all
            wire = size * frac
        per_op[op] = per_op.get(op, 0.0) + wire
        counts[op] = counts.get(op, 0) + 1
    return {"wire_bytes_per_op": per_op, "counts": counts,
            "wire_bytes": sum(per_op.values())}


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    args = out.get("argument_size_in_bytes", 0)
    alias = out.get("alias_size_in_bytes", 0)
    out["resident_bytes"] = args - alias + out.get("output_size_in_bytes", 0) \
        + out.get("temp_size_in_bytes", 0)
    return out


def model_flops(cfg, shape) -> float:
    n = cfg.n_active_params
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch          # decode: 1 token per seq


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path,
             force: bool = False) -> dict:
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    cfg, model = registry.get(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update({"ok": False, "skipped": True, "reason": why})
        out_path.write_text(json.dumps(rec, indent=1))
        return rec
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = int(np.prod(list(mesh.shape.values())))
    try:
        t0 = time.time()
        lowered = steps_lib.lower_cell(cfg, model, shape, mesh,
                                       multi_pod=multi)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):        # per-device list in new jax
            ca = ca[0] if ca else {}
        # trip-count-aware static profile of the partitioned module
        # (XLA's cost_analysis counts while bodies once — see hlo_analysis)
        cost, analyzer = analyze_hlo(compiled.as_text(), n_dev)
        flops = cost.flops
        bytes_acc = cost.hbm_bytes
        mem = memory_stats(compiled)
        mf = model_flops(cfg, shape)
        terms = {
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": cost.wire_bytes / ICI_BW,
        }
        dominant = max(terms, key=terms.get)
        bound_s = max(terms.values())
        rec.update({
            "ok": True, "n_devices": n_dev,
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "hlo_flops_per_dev": flops, "hlo_bytes_per_dev": bytes_acc,
            "collectives": {
                "wire_bytes_per_op": {k: round(v, 1) for k, v in
                                      cost.coll_bytes.items()},
                "counts": cost.coll_counts,
                "wire_bytes": cost.wire_bytes,
            },
            "top_collectives": analyzer.heaviest_collectives(10),
            "top_hbm": analyzer.heaviest_hbm(10),
            "xla_cost_analysis": {"flops": float(ca.get("flops", 0.0)),
                                  "bytes": float(ca.get("bytes accessed",
                                                        0.0))},
            "memory": mem,
            "model_flops_total": mf,
            "model_flops_per_dev": mf / n_dev,
            "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
            "terms": terms, "dominant": dominant,
            "roofline_fraction":
                (terms["compute_s"] / bound_s) if bound_s else 0.0,
        })
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    pin_host_platform()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list(registry.SHEET) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, mesh, out_dir, force=args.force)
                status = ("SKIP" if rec.get("skipped") else
                          "ok" if rec.get("ok") else "FAIL")
                extra = ""
                if rec.get("ok"):
                    extra = (f" dom={rec['dominant']}"
                             f" rf={rec['roofline_fraction']:.3f}"
                             f" compile={rec.get('compile_s', 0):.0f}s")
                elif not rec.get("skipped"):
                    extra = " " + rec.get("error", "")[:120]
                print(f"[{time.time()-t0:7.1f}s] {arch:22s} {shape:12s} "
                      f"{mesh:6s} {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
