#!/usr/bin/env python3
"""Readings that set the limits of a served model's comparison, on the
chip.

    python bench/control_lm.py --workload granite_alpaca_steady \
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 8

One process builds the cell once.  For each seed it serves the weights
made from that seed (handed to the same `Server`), runs a short window
at the cell's own load, and compares a seeded sample of what it served
with the plain reference (the program's readings).  For each control
seed the same positions are judged again with the token that the
reference computed in float8_e4m3 puts first (the control, one
precision below the bfloat16 the configuration serves in).  Each
reading is one JSON line on standard output.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from benchlib import boot, lm_weights
    cell, adp = boot.start(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    st = adp.setup(cell, seeds[0])
    for seed in seeds:
        st.seed = seed
        st.server.params = None
        st.server.params = st.make(lm_weights.key(seed))
        w = adp.window(st, args.seconds,
                       lambda name: contextlib.nullcontext())
        st.server.params = None
        rows = [("program", adp.check(st, w))]
        if seed in ctrl:
            rows.append(("control_fp8", adp.check(
                st, w, answer=adp.control_answer(cell.config))))
        for who, checks in rows:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "who": who, "failed": w["failed"],
                              "completed": len(w["done"]),
                              "readings": {n: v for n, v, _ in checks}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
