#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run_cell.py --workload twin_steady --seed 7 --seconds 30 \
        --trace 0

Everything the cell needs is found by name from `BENCHMARK.json` (see
`bench/benchlib/cells.py`).  The system under test is the `repro`
package under `src/`.  The persistent compilation cache is kept in
`bench/.jax_cache/` inside the checkout, at a fixed path, so only the
first run of a cell there compiles.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
The last line of standard output is the result object; the compared
numbers and their limits are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchlib import cells, runner
    t_start = runner.process_start_epoch()
    cell = cells.load(ROOT, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("run_cell: no system under test (src/repro) in this "
                 "checkout")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    runner.run(cell, seed=args.seed, seconds=args.seconds,
               traced=bool(args.trace), t_start=t_start)
    return 0


if __name__ == "__main__":
    sys.exit(main())
