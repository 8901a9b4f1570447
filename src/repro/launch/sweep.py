"""Process-parallel resumable dry-run sweep + the batched analytical roofline.

Two ways to cover the full backend design grid (10 archs x 4 shapes x 2
meshes = 80 cells), mirroring the device-side DSE batching pattern
(`core/scenarios.ScenarioSet`):

* ``run_sweep`` / CLI — fill ``results/dryrun/`` with REAL compiled
  artifacts (`repro.launch.dryrun.run_cell`) using a pool of **spawned**
  worker processes.  Resumable: cells whose artifact already parses as
  ok/skipped are never redone; failed or corrupt artifacts are retried
  (disable with ``retry_failed=False``).  Workers are spawned (never
  forked) so each initialises jax fresh, pinned to 512 virtual CPU
  devices (`mesh.pin_host_platform`) — the parent's jax state (if any)
  cannot leak a wrong device count into a compile, and no worker opens
  an attached accelerator.

* ``CellTable`` / ``analytical_terms`` — a struct-of-arrays ANALYTICAL
  roofline: first-order FLOPs / HBM / collective terms for every cell in
  ONE numpy pass over config-derived columns (no lowering, no compiles —
  the whole 80-cell grid evaluates in microseconds instead of ~80
  compiles).  ``analytical_cell`` is the per-cell loop path kept as the
  benchmark baseline (`benchmarks/roofline.backend_bench`).

* ``roofline_grid`` merges the two: compiled artifacts override the
  analytical terms wherever they exist (``source: "dryrun"`` vs
  ``"analytical"``).

Analytical model (first-order, per device; constants below):
  compute_s    = mult * n_active * tokens / n_dev / PEAK_FLOPS
                 (mult = 6 train, 2 prefill/decode; tokens = batch for
                 decode, batch*seq otherwise)
  memory_s     = (weight + activation + cache traffic) / HBM_BW
                 weights stream once per step (f32 train incl. grad +
                 optimizer traffic on the shard, bf16 serving), activations
                 ~8 d_model-sized touches per layer (16 with backward),
                 KV-cache / SSM-state traffic for decode/prefill.
  collective_s = wire bytes / ICI_BW
                 train: FSDP all-gather + grad reduce-scatter over the
                 16-wide model axis (+ cross-pod grad all-reduce on multi);
                 serving: 2 activation all-reduces per layer.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import sys
import time
from concurrent.futures import ProcessPoolExecutor, as_completed
from dataclasses import dataclass
from pathlib import Path

import numpy as np

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# TPU v5e-class hardware constants (per chip) — the single source of truth
# (repro.launch.dryrun re-exports these for the compiled path).
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

MESHES = ("single", "multi")
MESH_DEVICES = {"single": 256, "multi": 512}
MESH_PODS = {"single": 1, "multi": 2}
N_MODEL = 16                 # model-parallel axis width (launch.mesh)
N_DATA = 16                  # data-parallel axis width per pod

DONE_STATES = ("ok", "skipped")


# ---------------------------------------------------------------------------
# sweep bookkeeping (pure file inspection — safe in the parent process)
# ---------------------------------------------------------------------------

def all_cells(archs=None, shapes=None, meshes=MESHES) -> list[tuple]:
    """The full (arch, shape, mesh) grid, registry x shape order."""
    if archs is None or shapes is None:
        from ..configs.base import SHAPES
        from ..models import registry
        archs = list(registry.SHEET) if archs is None else archs
        shapes = list(SHAPES) if shapes is None else shapes
    return [(a, s, m) for a in archs for s in shapes for m in meshes]


def cell_status(out_dir, arch: str, shape: str, mesh: str) -> str:
    """missing | corrupt | failed | ok | skipped for one cell artifact."""
    f = Path(out_dir) / f"{arch}__{shape}__{mesh}.json"
    if not f.exists():
        return "missing"
    try:
        r = json.loads(f.read_text())
    except (json.JSONDecodeError, OSError):
        return "corrupt"
    if r.get("skipped"):
        return "skipped"
    return "ok" if r.get("ok") else "failed"


def pending_cells(cells=None, out_dir=RESULTS,
                  retry_failed: bool = True) -> list[tuple]:
    """Cells `run_sweep` would still execute (the resume set)."""
    cells = all_cells() if cells is None else cells
    redo = {"missing", "corrupt"} | ({"failed"} if retry_failed else set())
    return [c for c in cells if cell_status(out_dir, *c) in redo]


def _worker_init():
    # MUST precede the worker's first jax computation: jax fixes the
    # platform and the host device count when its backend starts
    from .mesh import pin_host_platform
    pin_host_platform()


def _worker_cell(cell: tuple, out_dir: str, force: bool) -> str:
    from . import dryrun                     # jax import happens here
    arch, shape, mesh = cell
    rec = dryrun.run_cell(arch, shape, mesh, Path(out_dir), force=force)
    if rec.get("skipped"):
        return "skipped"
    if rec.get("ok"):
        return "ok"
    return "failed: " + rec.get("error", "?")[:200]


def _cost_rank(cell: tuple) -> tuple:
    """Schedule heavy cells first so stragglers don't serialize the tail."""
    heavy = ("dbrx-132b", "yi-34b", "moonshot-v1-16b-a3b", "mamba2-2.7b")
    arch, shape, mesh = cell
    return (arch in heavy, shape.startswith("train"), mesh == "multi")


def run_sweep(out_dir=RESULTS, workers: int | None = None,
              force: bool = False, retry_failed: bool = True,
              archs=None, shapes=None, meshes=MESHES,
              progress=None) -> dict:
    """Fill the artifact directory, process-parallel and resumable.

    Returns {"scheduled", "ok", "skipped", "failed", "statuses"} where
    statuses maps each executed cell to its outcome.  A no-op resume
    (everything already done) spawns no workers at all.
    """
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    cells = all_cells(archs, shapes, meshes)
    todo = cells if force else pending_cells(cells, out_dir, retry_failed)
    todo = sorted(todo, key=_cost_rank, reverse=True)
    statuses: dict[tuple, str] = {}
    if todo:
        workers = workers or max(1, (mp.cpu_count() or 2) - 1)
        ctx = mp.get_context("spawn")        # fresh jax per worker
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                 initializer=_worker_init) as ex:
            futs = {ex.submit(_worker_cell, c, str(out_dir), force): c
                    for c in todo}
            t0 = time.time()
            for fut in as_completed(futs):
                cell = futs[fut]
                try:
                    st = fut.result()
                except Exception as e:  # noqa: BLE001 — keep sweeping
                    st = f"failed: {type(e).__name__}: {e}"
                statuses[cell] = st
                if progress:
                    progress(f"[{time.time() - t0:7.1f}s "
                             f"{len(statuses)}/{len(todo)}] "
                             f"{'__'.join(cell):45s} {st}")
    counts = {k: sum(1 for v in statuses.values() if v.startswith(k))
              for k in ("ok", "skipped", "failed")}
    return {"scheduled": len(todo), **counts, "statuses": statuses}


# ---------------------------------------------------------------------------
# batched analytical roofline (struct-of-arrays over arch x shape x mesh)
# ---------------------------------------------------------------------------

_COLS = ("n_active", "n_params", "d_model", "n_layers_eff", "seq", "batch",
         "n_dev", "n_pod", "kind", "applicable", "param_dtype_bytes",
         "cache_per_token", "state_bytes_per_seq")


@dataclass(frozen=True)
class CellTable:
    """Struct-of-arrays view of the (arch x shape x mesh) grid.

    Built once from the configs (the only per-arch Python loop), then
    `analytical_terms` evaluates the whole grid in one numpy pass —
    the backend-side analogue of ScenarioSet for the device DSE.
    """
    keys: tuple                     # ((arch, shape, mesh), ...) len N
    cols: dict                      # name -> (N,) float64 array

    def __len__(self) -> int:
        return len(self.keys)

    @classmethod
    def build(cls, archs=None, shapes=None, meshes=MESHES) -> "CellTable":
        from ..configs.base import SHAPES, shape_applicable
        from ..models import registry
        archs = list(registry.SHEET) if archs is None else list(archs)
        shape_names = list(SHAPES) if shapes is None else list(shapes)

        # one pass over archs (10), columns assembled per cell below
        acfg = {}
        for a in archs:
            cfg, _ = registry.get(a)
            layers_eff = cfg.n_layers + cfg.dec_layers
            kv_dim = cfg.n_kv_heads * cfg.head_dim
            if cfg.family == "ssm":
                cache_tok, state = 0.0, cfg.n_layers * cfg.ssm.d_inner \
                    * cfg.ssm.d_state * 2.0
            elif cfg.family == "hybrid":
                # shared attn block rides on top of the per-layer SSM state
                cache_tok = 2 * kv_dim * 2.0
                state = cfg.n_layers * cfg.ssm.d_inner * cfg.ssm.d_state * 2.0
            else:
                cache_tok, state = 2 * kv_dim * 2.0 * layers_eff, 0.0
            acfg[a] = (cfg, layers_eff, cache_tok, state)

        keys, rows = [], []
        for a in archs:
            cfg, layers_eff, cache_tok, state = acfg[a]
            for s in shape_names:
                shp = SHAPES[s]
                ok, _ = shape_applicable(cfg, shp)
                for m in meshes:
                    keys.append((a, s, m))
                    rows.append((
                        float(cfg.n_active_params), float(cfg.n_params),
                        float(cfg.d_model), float(layers_eff),
                        float(shp.seq_len), float(shp.global_batch),
                        float(MESH_DEVICES[m]), float(MESH_PODS[m]),
                        {"train": 0.0, "prefill": 1.0,
                         "decode": 2.0}[shp.kind],
                        float(ok),
                        4.0 if shp.kind == "train" else 2.0,
                        cache_tok, state))
        arr = np.asarray(rows, np.float64)
        return cls(tuple(keys),
                   {c: arr[:, i] for i, c in enumerate(_COLS)})


def analytical_terms(table: CellTable) -> dict:
    """The whole grid's roofline terms in one vectorized numpy pass.

    Returns (N,) arrays: compute_s / memory_s / collective_s / bound_s,
    plus `dominant` (str array) and the `applicable` mask.  Inapplicable
    cells (long_500k on quadratic archs) carry NaN terms.
    """
    c = table.cols
    train = c["kind"] == 0.0
    decode = c["kind"] == 2.0
    tokens = np.where(decode, c["batch"], c["batch"] * c["seq"])
    mult = np.where(train, 6.0, 2.0)
    compute_s = mult * c["n_active"] * tokens / c["n_dev"] / PEAK_FLOPS

    param_bytes = c["n_params"] * c["param_dtype_bytes"]
    weight = param_bytes * np.where(train, 3.0, 1.0)
    act = tokens / c["n_dev"] * c["d_model"] * c["n_layers_eff"] * 2.0 \
        * np.where(train, 16.0, 8.0)
    cache = (c["cache_per_token"] * c["seq"] + c["state_bytes_per_seq"]) \
        * c["batch"] / c["n_dev"] * (~train)
    memory_s = (weight + act + cache) / HBM_BW

    frac_m = (N_MODEL - 1) / N_MODEL
    pod_frac = (c["n_pod"] - 1) / c["n_pod"]
    wire_train = 2.0 * param_bytes * frac_m \
        + 2.0 * param_bytes / N_MODEL * pod_frac
    wire_serve = 2.0 * c["n_layers_eff"] \
        * tokens / (N_DATA * c["n_pod"]) * c["d_model"] * 2.0 * 2.0 * frac_m
    collective_s = np.where(train, wire_train, wire_serve) / ICI_BW

    app = c["applicable"] > 0.5
    nan = np.where(app, 1.0, np.nan)
    terms = {"compute_s": compute_s * nan, "memory_s": memory_s * nan,
             "collective_s": collective_s * nan}
    stacked = np.stack([terms["compute_s"], terms["memory_s"],
                        terms["collective_s"]])
    bound = np.max(stacked, axis=0)
    names = np.array(["compute_s", "memory_s", "collective_s"])
    dom = names[np.argmax(np.where(np.isnan(stacked), -np.inf, stacked),
                          axis=0)]
    return {**terms, "bound_s": bound, "dominant": dom, "applicable": app}


def analytical_cell(arch: str, shape: str, mesh: str = "single") -> dict:
    """Per-cell analytical roofline — the loop-path baseline that the
    batched `analytical_terms` is benchmarked against (BENCH_backend).
    Rebuilds the config and evaluates a 1-row table per call, exactly the
    per-cell cost the batched path amortizes away."""
    t = CellTable.build([arch], [shape], [mesh])
    terms = analytical_terms(t)
    return {k: (v[0] if isinstance(v, np.ndarray) else v)
            for k, v in terms.items()}


def roofline_grid(results_dir=None, table: CellTable | None = None) -> list:
    """One row per grid cell: compiled artifact terms where an ok dry-run
    artifact exists (source="dryrun"), analytical terms otherwise
    (source="analytical"; inapplicable cells carry source="skip")."""
    d = Path(results_dir) if results_dir else RESULTS
    table = table or CellTable.build()
    terms = analytical_terms(table)
    rows = []
    for i, (arch, shape, mesh) in enumerate(table.keys):
        row = {"arch": arch, "shape": shape, "mesh": mesh}
        f = d / f"{arch}__{shape}__{mesh}.json"
        rec = None
        if f.exists():
            try:
                rec = json.loads(f.read_text())
            except (json.JSONDecodeError, OSError):
                rec = None
        if rec and rec.get("ok") and rec.get("terms"):
            t = rec["terms"]
            row.update({"source": "dryrun",
                        **{k: t[k] for k in ("compute_s", "memory_s",
                                             "collective_s")},
                        "bound_s": max(t.values()),
                        "dominant": max(t, key=t.get)})
        elif not terms["applicable"][i]:
            row.update({"source": "skip"})
        else:
            row.update({"source": "analytical",
                        "compute_s": float(terms["compute_s"][i]),
                        "memory_s": float(terms["memory_s"][i]),
                        "collective_s": float(terms["collective_s"][i]),
                        "bound_s": float(terms["bound_s"][i]),
                        "dominant": str(terms["dominant"][i])})
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-retry-failed", action="store_true")
    args = ap.parse_args(argv)
    archs = None if args.arch == "all" else [args.arch]
    shapes = None if args.shape == "all" else [args.shape]
    meshes = MESHES if args.mesh == "both" else (args.mesh,)
    res = run_sweep(Path(args.out), workers=args.workers, force=args.force,
                    retry_failed=not args.no_retry_failed,
                    archs=archs, shapes=shapes, meshes=meshes,
                    progress=lambda s: print(s, flush=True))
    print(f"scheduled={res['scheduled']} ok={res['ok']} "
          f"skipped={res['skipped']} failed={res['failed']}", flush=True)
    return 1 if res["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
