"""Find everything a cell needs by name, from `BENCHMARK.json`.

A cell names a configuration and a traffic mix.  The configuration's
file (`configs[].file`) names the adapter that runs it (`bench/adapters/
<adapter>.py`); the traffic mix is `bench/traffic/<traffic>.json`; each
per-layer metric is read by `bench/metrics/<name>.py`; each kernel's
work is counted by `bench/work/<kernel>.py`; the device peaks are in
`bench/peaks.json`.  Adding a cell, a mix, a configuration, a metric or
a kernel adds files and entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # metric entries this cell reports
    per_layer: list
    bench_dir: Path = BENCH


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    root = Path(root)
    bench_dir = root / "bench"
    b = benchmark(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in b["configs"]}
    conf = confs[w["config"]]
    e2e = [m for m in b["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per = [m for m in b["per_layer"]
           if _applies(m, workload) and m["moves"] in moved]
    return Cell(workload, int(w["chips"]), load_json(root / conf["file"]),
                load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
                e2e, per, bench_dir)


def module(kind: str, name: str, bench_dir: Path = BENCH):
    """Load `bench/<kind>/<name>.py` (names may hold dots)."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} file {path.name} under bench/{kind}/")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str, bench_dir: Path = BENCH) -> dict:
    table = load_json(Path(bench_dir) / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(have {sorted(table)})")
    return table[kind]
