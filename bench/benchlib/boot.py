"""Start a script that drives one cell in one process: the system under
test on the path, JAX's persistent compile cache at the checkout's fixed
`bench/.jax_cache/`, the cell found by name, its chips looked for, its
adapter loaded."""
from __future__ import annotations

import os
import sys
from pathlib import Path

from . import cells, runner

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def start(workload: str):
    """(cell, adapter) of `workload`, with JAX configured as a run of
    the cell configures it."""
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH / ".jax_cache")
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cells.load(ROOT, workload)
    runner.devices(cell.chips)
    return cell, cells.module("adapters", cell.config["adapter"],
                              cell.bench_dir)
