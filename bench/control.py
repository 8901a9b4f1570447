#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison, on the chip.

    python bench/control.py --workload twin_steady --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 6

One process builds the cell once, then for each seed runs a short
window at the cell's own load and compares a seeded sample of what it
answered with the plain reference (the program's readings).  For each
control seed the same sample is answered again by the reference
computed in bfloat16, the precision below the float32 that the
configurations state (the control's readings).  Each reading is one
JSON line on standard output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import ml_dtypes
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchlib import cells, runner
    cell = cells.load(ROOT, args.workload)
    runner.devices(cell.chips)
    adp = cells.module("adapters", cell.config["adapter"], cell.bench_dir)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    st = adp.setup(cell, seeds[0])
    for seed in seeds:
        st.seed = seed
        w = adp.window(st, args.seconds,
                       lambda name: contextlib.nullcontext())
        rows = [("program", adp.check(st, w))]
        if seed in ctrl:
            rows.append(("control_bf16", adp.check(
                st, w, answer=adp.control_answer(cell.config,
                                                 ml_dtypes.bfloat16))))
        for who, checks in rows:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "who": who, "failed": w["failed"],
                              "readings": {n: v for n, v, _ in checks}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
