"""SSPerf hillclimb harness: re-lower one cell with config-variant knobs and
re-derive the roofline terms (hypothesis -> change -> measure -> validate).

Variants are plain ModelConfig field overrides (the knobs in configs/base):
  sp          sequence_parallel=True     (Megatron-SP residual stream)
  seqattn     attn_seq_shard=True        (context-parallel attention)
  dots        remat_policy="dots"        (save matmuls, skip recompute)
  ck<j>x<k>   attn_chunk_q=j, attn_chunk_k=k
  ssd<c>      ssm chunk = c
  ce<c>       ce_chunk = c

Results land in results/perf/<arch>__<shape>__<variant>.json; the log in
EXPERIMENTS.md SSPerf is written from these.
"""
import argparse
import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from ..configs.base import SHAPES
from ..models import registry
from . import steps as steps_lib
from .dryrun import PEAK_FLOPS, HBM_BW, ICI_BW, memory_stats, model_flops
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh, pin_host_platform


def apply_variant(cfg, overrides: dict):
    ssm_over = overrides.pop("ssm_chunk", None)
    if ssm_over and cfg.ssm is not None:
        overrides["ssm"] = dataclasses.replace(cfg.ssm, chunk=ssm_over)
    return dataclasses.replace(cfg, **overrides)


def run_variant(arch: str, shape_name: str, variant: str, overrides: dict,
                out_dir="results/perf", mesh_name="single"):
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{arch}__{shape_name}__{variant}.json"
    if path.exists():
        return json.loads(path.read_text())
    cfg, model = registry.get(arch)
    cfg = apply_variant(cfg, dict(overrides))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "overrides": {k: str(v) for k, v in overrides.items()}}
    try:
        lowered = steps_lib.lower_cell(cfg, model, shape, mesh)
        compiled = lowered.compile()
        cost, analyzer = analyze_hlo(compiled.as_text(), n_dev)
        terms = {"compute_s": cost.flops / PEAK_FLOPS,
                 "memory_s": cost.hbm_bytes / HBM_BW,
                 "collective_s": cost.wire_bytes / ICI_BW}
        bound = max(terms.values())
        rec.update({
            "ok": True, "compile_s": round(time.time() - t0, 1),
            "terms": terms,
            "dominant": max(terms, key=terms.get),
            "roofline_fraction": terms["compute_s"] / bound if bound else 0,
            "memory": memory_stats(compiled),
            "collectives": {k: round(v, 1)
                            for k, v in cost.coll_bytes.items()},
            "top_hbm": analyzer.heaviest_hbm(6),
            "top_collectives": analyzer.heaviest_collectives(6),
        })
    except Exception as e:  # noqa: BLE001
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}"})
    path.write_text(json.dumps(rec, indent=1))
    return rec


VARIANTS = {
    "baseline": {},
    "sp": {"sequence_parallel": True},
    "seqattn": {"attn_seq_shard": True},
    "sp+seqattn": {"sequence_parallel": True, "attn_seq_shard": True},
    "dots": {"remat_policy": "dots"},
    "sp+dots": {"sequence_parallel": True, "remat_policy": "dots"},
    "sp+seqattn+dots": {"sequence_parallel": True, "attn_seq_shard": True,
                        "remat_policy": "dots"},
    "ck1024x2048": {"attn_chunk_q": 1024, "attn_chunk_k": 2048},
    "sp+ck1024x2048": {"sequence_parallel": True, "attn_chunk_q": 1024,
                       "attn_chunk_k": 2048},
    "ssd128": {"ssm_chunk": 128},
    "ssd32": {"ssm_chunk": 32},
    "sp+ssd128": {"sequence_parallel": True, "ssm_chunk": 128},
    "ce256": {"ce_chunk": 256},
}


def main():
    pin_host_platform()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    rec = run_variant(args.arch, args.shape, args.variant,
                      VARIANTS[args.variant])
    if rec.get("ok"):
        t = rec["terms"]
        print(f"{args.arch} {args.shape} {args.variant}: "
              f"cmp={t['compute_s']:.3f} mem={t['memory_s']:.3f} "
              f"col={t['collective_s']:.3f} rf={rec['roofline_fraction']:.3f}")
    else:
        print("FAIL", rec.get("error"))


if __name__ == "__main__":
    main()
