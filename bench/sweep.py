#!/usr/bin/env python3
"""Find a cell's knee: its window at several fixed rates, on the chip.

    python bench/sweep.py --workload granite_alpaca_steady \
        --rates 4,5,6 --runs 3 --seconds 51 --seed 11

One process builds the cell once, then runs `--runs` windows at each
Poisson rate (requests/s) in place of the mix's own, each with its own
seed.  Each window is one JSON line: its latency quantiles, the
requests still queued (due, not yet taken by a `run()` call) and in
flight at the window's close, the batches and their mean size, and when
the last request finished.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from benchlib import boot
    cell, adp = boot.start(args.workload)
    st = adp.setup(cell, args.seed)
    mix = st.mix
    k = 0
    for rate in [float(r) for r in args.rates.split(",")]:
        for _ in range(args.runs):
            st.mix = dict(mix, arrivals={"kind": "poisson",
                                         "rate_per_s": rate})
            st.seed = args.seed + 1 + k
            k += 1
            w = adp.window(st, args.seconds,
                           lambda name: contextlib.nullcontext())
            lat = [d["latency_s"] * 1e3 for d in w["done"]]
            n = w["notes"]
            print(json.dumps({
                "workload": args.workload, "rate_per_s": rate,
                "seed": st.seed, "requests": w["attempted"],
                "failed": w["failed"],
                "p50_ms": float(np.quantile(lat, 0.5)),
                "p95_ms": float(np.quantile(lat, 0.95)),
                "queued_at_close": n["queued_at_close"],
                "in_flight_at_close": n["in_flight_at_close"],
                "batches": n["batches"],
                "items_per_batch": n["items_per_batch"],
                "last_completion_s": n["last_completion_s"],
                "compiles_in_window": n["compiles_in_window"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
