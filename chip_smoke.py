#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU and check every answer.

    python chip_smoke.py              # one chip: the four phases below
    python chip_smoke.py --chips 4    # four chips: the sharded fleet only

One process holds the chip for the whole run.  Phases, in order:

  1. twin_xla     `DesignTwin(dt_s=20)` on the default grid (63 combos in
                  bucket 64, 2160 steps, 3 throttle levels): warm-up,
                  four value-level what-ifs, one repeated, and one
                  `what_if_many` batch of four.  No retrace after the
                  warm-up; every combo checked against the host oracle
                  `daysim.reference_integrate`, the front against the
                  host `dse.non_dominated`.
  2. twin_pallas  the same grid through the pallas day-scan kernel; the
                  program that ran must hold the compiled kernel
                  (`tpu_custom_call`) and give phase 1's front and
                  survival flags.
  3. fleet        `fleet_day` over 100,000 sampled users at dt_s=60; the
                  first 64 users checked against `fleet.reference_fleet`.
  4. backend      granite-3-2b at published widths with random weights,
                  four requests through `serving.engine.Server`, checked
                  against `transformer.forward` at highest precision.

`--chips 4` runs only the sharded fleet: the same 100,000 users with
`n_shards=4` against `n_shards=1`.

Each phase prints its setup time (first call: trace, compile or cache
read), its warm wall time (results fetched to the host), the trace
counters and each check.  A failed check exits non-zero at once.  The
script refuses to start without a TPU.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

TWIN_DT_S = 20.0
FLEET_USERS = 100_000
FLEET_DT_S = 60.0
FLEET_REF_USERS = 64
BACKEND_ARCH = "granite-3-2b"
PROMPT_LENS = (8, 16, 24, 32)
NEW_TOKENS = 16
SEED = 0

# Stated tolerances.  Twin against the float32 host oracle: the device
# and numpy round exp() and fused multiply-adds differently, so a
# battery-empty crossing may move by a step; peaks agree far inside
# 0.01 C.  Fleet curves: the parity bound of tests/test_fleet.py.
# Backend logits are bfloat16 through 40 layers: the engine's cached
# decode path and the full-sequence forward round differently, a few
# bf16 ulps at the top of the logit range.
TTE_TOL_STEPS = 2
PEAK_TOL_C = 1e-2
CURVE_RTOL = 1e-6
LOGIT_TOL = 0.03            # of max |reference logit|


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip(),
          flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {name} {detail}")


def say(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phase 0: the wearable model's measured FLOPs
# ---------------------------------------------------------------------------

_FLOPS_CHILD = ("import json; from repro.perception import nets; "
                "print(json.dumps(nets.measured_flops()))")


def phase_flops() -> None:
    """The FLOPs table the power model uses must be the host CPU's,
    whatever device this process holds: compare it with a derivation
    in a child process that sees only the CPU (it never opens the
    chip)."""
    from repro.perception import nets
    say("[flops] measured FLOPs of the perception nets (host CPU backend)")
    table, secs = timed(nets.measured_flops)
    say(f"  derive_s={secs:.3f} " + json.dumps(table))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", _FLOPS_CHILD], env=env,
                         capture_output=True, text=True, timeout=900,
                         check=True)
    fresh = json.loads(out.stdout.strip().splitlines()[-1])
    check("flops_equal_cpu_only_process", table == fresh, json.dumps(fresh))


# ---------------------------------------------------------------------------
# phases 1-2: the design twin
# ---------------------------------------------------------------------------

def _what_if_policies(i: int) -> tuple:
    """Value-level what-if: new trip points, same grid shape."""
    from repro.core import daysim
    gov = daysim.get_policy("thermal_governor")
    saver = daysim.get_policy("battery_saver")
    return ("none",
            dataclasses.replace(gov, name=f"gov{i}",
                                temp_trip_c=gov.temp_trip_c + 0.25 * i),
            dataclasses.replace(saver, name=f"saver{i}",
                                soc_trip=saver.soc_trip - 0.01 * i))


def _oracle_report(grid: dict, dt_s: float):
    """The grid's report from the host oracle: legacy host tables, one
    `reference_integrate` per combo, numpy summary."""
    import numpy as np
    from repro.core import daysim
    combos, skipped = daysim.build_combos(**grid)
    tables = daysim.batch_tables(combos, dt_s)
    host = {k: (np.asarray(v) if k != "const"
                else {c: np.asarray(x) for c, x in v.items()})
            for k, v in tables.items()}
    runs = []
    for i in range(len(combos)):
        tb = {k: (v[i] if k != "const"
                  else {c: x[i] for c, x in v.items()})
              for k, v in host.items()}
        runs.append(daysim.reference_integrate(tb))
    ys = {k: np.stack([r[k] for r in runs]) for k in runs[0]}
    summ = daysim._summarize(ys, host, dt_s)
    return daysim.DayReport(
        combos=[cb.label() for cb in combos],
        steady_mw=np.asarray([cb.steady_mw for cb in combos]),
        n_users=1e6, dt_s=dt_s, skipped=skipped, **summ)


def _check_against_oracle(tag: str, rep, ref) -> None:
    import numpy as np
    from repro.core import dse
    check(f"{tag}_combo_order", rep.combos == ref.combos,
          f"n={len(rep)}")
    d_tte = float(np.abs(rep.time_to_empty_h - ref.time_to_empty_h).max())
    d_peak = float(np.abs(rep.peak_skin_c - ref.peak_skin_c).max())
    surv = rep.survives()
    check(f"{tag}_survives_vs_reference_integrate",
          np.array_equal(surv, ref.survives()),
          f"survivors={int(surv.sum())}/{len(surv)}")
    step_h = ref.dt_s / 3600.0
    check(f"{tag}_time_to_empty", d_tte <= TTE_TOL_STEPS * step_h + 1e-9,
          f"max|d|={d_tte!r} h (tol {TTE_TOL_STEPS} steps)")
    check(f"{tag}_peak_skin", d_peak <= PEAK_TOL_C,
          f"max|d|={d_peak!r} C (tol {PEAK_TOL_C})")
    host_front = dse.non_dominated(rep.objectives(), maximize=(0,))
    check(f"{tag}_front_vs_host_non_dominated",
          np.array_equal(rep.front_mask, host_front),
          f"front={int(host_front.sum())}")


def _differences(a, b) -> str:
    """How two reports of one grid differ, field by field."""
    import numpy as np

    def most(x, y):
        return repr(float(np.abs(x - y).max()))
    return (f"front_equal={np.array_equal(a.front_mask, b.front_mask)} "
            f"survives_equal={np.array_equal(a.survives(), b.survives())} "
            f"max|d tte|={most(a.time_to_empty_h, b.time_to_empty_h)} h "
            f"max|d peak|={most(a.peak_skin_c, b.peak_skin_c)} C "
            f"max|d pod_hours|={most(a.pod_hours, b.pod_hours)}")


def _same_answer(a, b) -> bool:
    import numpy as np
    return (np.array_equal(a.front_mask, b.front_mask)
            and np.array_equal(a.survives(), b.survives())
            and np.array_equal(a.time_to_empty_h, b.time_to_empty_h)
            and np.array_equal(a.peak_skin_c, b.peak_skin_c))


def phase_twin_xla(grid: dict, dt_s: float):
    from repro.core import daysim
    from repro.serving.twin import DesignTwin
    say(f"[twin_xla] DesignTwin(dt_s={dt_s}) backend=xla")
    twin, setup = timed(DesignTwin, dt_s=dt_s, **grid)
    base = twin.query()
    n = len(base)
    steps = int(round(base.day_hours.max() * 3600.0 / dt_s))
    say(f"  combos={n} bucket={daysim.bucket_size(n)} steps={steps} "
        f"setup_s={setup:.3f}")
    queries = [{"policies": _what_if_policies(i)} for i in range(1, 5)]
    # warm the batch shape with other values, off the clock
    _, batch_setup = timed(
        twin.what_if_many,
        [{"policies": _what_if_policies(i)} for i in range(11, 15)])
    say(f"  batch_setup_s={batch_setup:.3f}")
    traces0 = daysim.EXEC_STATS["traces"]
    serial = []
    for q in queries:
        rep, secs = timed(twin.what_if, **q)
        serial.append(rep)
        say(f"  what_if warm_s={secs:.4f}")
    again, secs = timed(twin.what_if, **queries[-1])
    say(f"  what_if repeated warm_s={secs:.4f}")
    batch, secs = timed(twin.what_if_many, queries)
    say(f"  what_if_many K={len(queries)} warm_s={secs:.4f}")
    traces = daysim.EXEC_STATS["traces"] - traces0
    say(f"  exec_stats={daysim.EXEC_STATS}")
    check("twin_no_retrace_after_warmup", traces == 0, f"traces={traces}")
    check("twin_repeated_query_identical", _same_answer(again, serial[-1]))
    base_ref, oracle_s = timed(_oracle_report, grid, dt_s)
    say(f"  oracle_s_per_grid={oracle_s:.3f}")
    _check_against_oracle("twin_base", base, base_ref)
    for i, (q, rep, brep) in enumerate(zip(queries, serial, batch)):
        ref = _oracle_report({**grid, **q}, dt_s)
        _check_against_oracle(f"twin_what_if{i}", rep, ref)
        _check_against_oracle(f"twin_batch{i}", brep, ref)
        say(f"  batch{i} vs serial: {_differences(brep, rep)}")
    return base, base_ref


def phase_twin_pallas(grid: dict, dt_s: float, base, base_ref) -> None:
    import numpy as np
    from repro.core import daysim
    from repro.serving.twin import DesignTwin
    say(f"[twin_pallas] DesignTwin(dt_s={dt_s}) backend=pallas")
    twin, setup = timed(DesignTwin, dt_s=dt_s, backend="pallas", **grid)
    rep, secs = timed(twin.query)
    say(f"  setup_s={setup:.3f} warm_query_s={secs:.4f}")
    # lower the cached program of this query again: the text of the
    # executable that just ran
    kw = {**daysim._batch_defaults(), **grid, "dt_s": dt_s}
    pipe = daysim._fused_pipeline(**kw, backend="pallas")
    text = pipe.fn.lower(pipe.dyn, pipe.ix).as_text()
    check("pallas_kernel_compiled", "tpu_custom_call" in text)
    say(f"  vs xla: {_differences(rep, base)}")
    _check_against_oracle("pallas", rep, base_ref)
    check("pallas_front_equals_xla",
          np.array_equal(rep.front_mask, base.front_mask))
    check("pallas_survives_equals_xla",
          np.array_equal(rep.survives(), base.survives()))


# ---------------------------------------------------------------------------
# phase 3: the fleet scan
# ---------------------------------------------------------------------------

def _curves_close(a, b) -> tuple:
    import numpy as np
    scale = max(1.0, float(np.abs(b).max()))
    err = float(np.abs(a - b).max())
    ok = np.allclose(a, b, rtol=CURVE_RTOL, atol=CURVE_RTOL * scale)
    return bool(ok), f"max|d|={err!r} scale={scale!r}"


def check_integral(tag: str, rep) -> None:
    """The curve's time integral is the fleet's pod-hours: float32
    bins summed over all users against per-user totals summed on the
    host in float64."""
    bin_hours = 24.0 / rep.curve.shape[0]
    rel = float(abs(rep.curve_total.sum() * bin_hours
                    / rep.pod_hours.sum() - 1))
    check(f"{tag}_curve_integral_is_pod_hours", rel <= CURVE_RTOL,
          f"rel={rel!r}")


def phase_fleet(n_users: int, dt_s: float, n_ref: int) -> None:
    import numpy as np
    from repro.core import fleet
    say(f"[fleet] fleet_day({n_users} users, dt_s={dt_s})")
    rep, setup = timed(fleet.fleet_day, fleet.DEFAULT_POPULATION, n_users,
                       key=SEED, dt_s=dt_s)
    traces0 = fleet.FLEET_STATS["traces"]
    again, secs = timed(fleet.fleet_day, fleet.DEFAULT_POPULATION, n_users,
                        key=SEED, dt_s=dt_s)
    say(f"  n_shards={rep.n_shards} setup_s={setup:.3f} warm_s={secs:.3f} "
        f"survival_rate={rep.survival_rate()!r}")
    check("fleet_no_retrace",
          fleet.FLEET_STATS["traces"] == traces0)
    check("fleet_repeat_identical",
          np.array_equal(rep.time_to_empty_h, again.time_to_empty_h)
          and np.array_equal(rep.curve, again.curve))
    check_integral("fleet", rep)
    sub = rep.population.take(np.arange(n_ref))
    small = fleet.fleet_day(sub, dt_s=dt_s)
    ref, ref_s = timed(fleet.reference_fleet, sub, dt_s=dt_s)
    say(f"  reference_fleet({n_ref} users) s={ref_s:.3f} "
        f"survivors={int(ref.survives().sum())}/{n_ref}")
    check("fleet_survives_vs_reference",
          np.array_equal(rep.survives()[:n_ref], ref.survives()))
    check("fleet_shutdown_vs_reference",
          np.array_equal(rep.shutdown[:n_ref], ref.shutdown))
    check("fleet_subset_equals_full_run",
          np.array_equal(small.survives(), ref.survives())
          and np.array_equal(small.time_to_empty_h,
                             rep.time_to_empty_h[:n_ref]))
    d_tte = float(np.abs(rep.time_to_empty_h[:n_ref]
                         - ref.time_to_empty_h).max())
    d_peak = float(np.abs(rep.peak_skin_c[:n_ref] - ref.peak_skin_c).max())
    say(f"  vs reference: max|d tte|={d_tte!r} h max|d peak|={d_peak!r} C")
    ok, detail = _curves_close(small.curve, ref.curve)
    check("fleet_curve_vs_reference", ok, detail)
    ok, detail = _curves_close(small.stream_curve, ref.stream_curve)
    check("fleet_stream_curve_vs_reference", ok, detail)


def phase_fleet_sharded(n_users: int, dt_s: float, n_shards: int) -> None:
    import numpy as np
    from repro.core import fleet
    say(f"[fleet_sharded] fleet_day({n_users} users, dt_s={dt_s}) "
        f"n_shards={n_shards} vs 1")
    pop = fleet.sample_population(fleet.DEFAULT_POPULATION, n_users, SEED)
    reps = {}
    for k in (n_shards, 1):
        rep, setup = timed(fleet.fleet_day, pop, dt_s=dt_s, n_shards=k)
        reps[k], secs = timed(fleet.fleet_day, pop, dt_s=dt_s, n_shards=k)
        say(f"  n_shards={k} user_devices={rep.user_devices} "
            f"setup_s={setup:.3f} warm_s={secs:.3f}")
    a, b = reps[n_shards], reps[1]
    check("sharded_outputs_spread", a.user_devices == n_shards,
          f"user_devices={a.user_devices}")
    check_integral("sharded", a)
    check("sharded_survives_equal", np.array_equal(a.survives(),
                                                   b.survives()),
          f"survival_rate={a.survival_rate()!r}")
    for name in ("curve", "stream_curve"):
        ok, detail = _curves_close(getattr(a, name), getattr(b, name))
        check(f"sharded_{name}_equal", ok, detail)


# ---------------------------------------------------------------------------
# phase 4: one backend model at published widths
# ---------------------------------------------------------------------------

def phase_backend(arch: str, smoke: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import registry
    from repro.nn import core
    from repro.serving.engine import Request, Server
    cfg, model = registry.get(arch, smoke=smoke)
    say(f"[backend] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model}"
        f" heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
        f"param_dtype={jnp.dtype(cfg.param_dtype).name} "
        f"compute_dtype={jnp.dtype(cfg.compute_dtype).name}")
    init = jax.jit(lambda k: model.init(k, cfg))
    params, init_s = timed(
        lambda: jax.block_until_ready(init(jax.random.PRNGKey(SEED))))
    say(f"  params={core.count_params(params)} "
        f"({core.param_bytes(params) / 1e9:.3f} GB) init_s={init_s:.3f}")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(2, cfg.vocab, size=n).astype(np.int32)
               for n in PROMPT_LENS]
    srv = Server(cfg, model, params, batch_slots=len(prompts),
                 max_len=64, eos=-1)

    def serve():
        for i, p in enumerate(prompts):
            srv.submit(Request(i, p, max_new_tokens=NEW_TOKENS))
        return srv.run()

    first, setup = timed(serve)
    done, secs = timed(serve)
    say(f"  serve {len(prompts)} requests x {NEW_TOKENS} tokens: "
        f"setup_s={setup:.3f} warm_s={secs:.3f} stats={srv.stats}")
    check("backend_tokens",
          all(len(r.out_tokens) == NEW_TOKENS for r in done))
    check("backend_deterministic",
          [r.out_tokens for r in done] == [r.out_tokens for r in first])
    # the engine's last prompt-position logits (its own prefill path)
    # against the full-sequence forward over the same left-padded rows
    reqs = [Request(i, p) for i, p in enumerate(prompts)]
    logits, _, s_len = srv._prefill_batch(reqs)
    toks = np.zeros((len(prompts), s_len), np.int32)
    for i, p in enumerate(prompts):
        toks[i, s_len - len(p):] = p
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: core.unembed_logits(
            p["embed"]["table"],
            model.forward(p, cfg, t, remat=False)[0][:, -1]))(
                params, jnp.asarray(toks))
    eng = np.asarray(logits, np.float32)
    ref = np.asarray(ref, np.float32)
    check("backend_logits_finite", bool(np.isfinite(eng).all()
                                        and np.isfinite(ref).all()))
    err, top = float(np.abs(eng - ref).max()), float(np.abs(ref).max())
    check("backend_logits_vs_forward", err <= LOGIT_TOL * top,
          f"max|d|={err!r} max|ref|={top!r} (tol {LOGIT_TOL} x max|ref|)")
    srt = np.sort(ref, axis=1)
    say(f"  reference top1-top2 margins={(srt[:, -1] - srt[:, -2]).tolist()}")
    want = ref.argmax(axis=1).tolist()
    check("backend_greedy_token",
          eng.argmax(axis=1).tolist() == want
          and [r.out_tokens[0] for r in done] == want, f"tokens={want}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"  device peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------

def _entries(cache: Path) -> int:
    return sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fleet sharded over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")
    from repro import compat
    cache = compat.enable_persistent_cache()
    say(f"device={dev.device_kind} count={len(devices)} jax={jax.__version__}"
        f" compile_cache={cache} entries={_entries(cache)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_fleet_sharded(FLEET_USERS, FLEET_DT_S, n_shards=4)
    else:
        phase_flops()
        base, base_ref = phase_twin_xla({}, TWIN_DT_S)
        phase_twin_pallas({}, TWIN_DT_S, base, base_ref)
        phase_fleet(FLEET_USERS, FLEET_DT_S, FLEET_REF_USERS)
        phase_backend(BACKEND_ARCH)
    say(f"total_s={time.perf_counter() - t0:.3f} "
        f"compile_cache_entries={_entries(cache)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
