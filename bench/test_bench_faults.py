"""The comparison that decides `correct`, shown to fail.

On the CPU at a size a test run can hold, each test drives the rest of
a run (`runner.run` without the look for a TPU) and checks the verdict:
the program as it is reads correct; the reference computed in bfloat16
(the control, one precision below the float32 the configurations state)
in the program's place reads not correct; and so does the program with
its timed path broken underneath in each way the cell can break."""
from __future__ import annotations

import io
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import cells, runner  # noqa: E402


FLEET_E2E = [{"name": "user_days_per_s", "unit": "user-days/s"},
             {"name": "setup_s", "unit": "s"}]


def fleet_cell(traffic: str = "draws_100k") -> cells.Cell:
    """The fleet path, which no cell of `BENCHMARK.json` runs yet (no
    public source defines its population), from its files."""
    return cells.Cell("fleet_mc", 1,
                      cells.load_json(BENCH / "configs"
                                      / "fleet_world_mix.json"),
                      cells.load_json(BENCH / "traffic" / f"{traffic}.json"),
                      FLEET_E2E, [], BENCH)


def tiny(workload: str, **traffic):
    cell = fleet_cell() if workload == "fleet_mc" else cells.load(ROOT,
                                                                   workload)
    if cell.config["adapter"] == "twin":
        cell.config = dict(cell.config, dt_s=120.0, batch_window=2)
        cell.traffic = dict(cell.traffic, checked=2,
                            arrivals={"kind": "poisson", "rate_per_s": 3.0})
    else:
        cell.traffic = dict(cell.traffic, n_users=1024, n_shards=1,
                            checked=1)
    cell.traffic.update(traffic)
    return cell


def run(cell, keep=None, seconds=1.0):
    from repro.core import daysim, fleet
    daysim.clear_exec_cache()
    fleet._fleet_runner.cache_clear()
    return runner.run(cell, seed=2**33 + 17, seconds=seconds, traced=False,
                      platform="cpu", out=io.StringIO(), err=io.StringIO(),
                      keep=keep)


def failed_checks(line):
    return [n for n, c in line["checks"].items() if c["value"] > c["limit"]]


def control_fails(keep, cell):
    import ml_dtypes
    adp = cells.module("adapters", cell.config["adapter"])
    checks = adp.check(keep["state"], keep["window"],
                       answer=adp.control_answer(cell.config,
                                                 ml_dtypes.bfloat16))
    return [n for n, v, lim in checks if v > lim]


def test_twin_program_passes_and_control_fails():
    cell = tiny("twin_steady")
    keep = {}
    line = run(cell, keep)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert control_fails(keep, cell)


def test_twin_answer_altered_where_produced(monkeypatch):
    from repro.core import daysim
    orig = daysim._host_summary

    def altered(summ, n_real):
        front, steady, host = orig(summ, n_real)
        host["time_to_empty_h"] = host["time_to_empty_h"] + 3 * 120 / 3600
        return front, steady, host
    monkeypatch.setattr(daysim, "_host_summary", altered)
    line = run(tiny("twin_steady"))
    assert not line["correct"]
    assert "tte_steps" in failed_checks(line)


def test_twin_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.core import daysim
    orig = daysim._step_math

    def stuck(carry, x, const):
        _, out = orig(carry, x, const)
        return carry, out
    monkeypatch.setattr(daysim, "_step_math", stuck)
    line = run(tiny("twin_steady"))
    assert not line["correct"]


def test_twin_table_derived_otherwise_than_the_file_states(monkeypatch):
    from repro.core import offload
    orig = offload.stream_rates

    def shifted(*args, **kw):
        rates = orig(*args, **kw)
        return dict(rates, tok_per_cap=rates["tok_per_cap"] * 1.001)
    monkeypatch.setattr(offload, "stream_rates", shifted)
    line = run(tiny("twin_steady"))
    assert not line["correct"]
    assert "config_drift" in failed_checks(line)


def test_fleet_program_passes_and_control_fails():
    cell = tiny("fleet_mc")
    keep = {}
    line = run(cell, keep)
    assert line["correct"], line["checks"]
    assert control_fails(keep, cell)


def test_fleet_half_of_the_users_left_out(monkeypatch):
    from repro.core import fleet
    orig = fleet.fleet_day

    def half(pop, fleet_size=None, **kw):
        return orig(pop.take(np.arange(len(pop) // 2)),
                    fleet_size=float(len(pop)), **kw)
    monkeypatch.setattr(fleet, "fleet_day", half)
    line = run(tiny("fleet_mc"))
    assert not line["correct"]


def test_fleet_answer_altered_where_produced(monkeypatch):
    from repro.core import fleet
    orig = fleet._bin_sums
    monkeypatch.setattr(fleet, "_bin_sums",
                        lambda x, b, n: orig(x, b, n) * 1.001)
    line = run(tiny("fleet_mc"))
    assert not line["correct"]
    assert "curve_rel" in failed_checks(line)


EXCHANGE = textwrap.dedent("""
    import io, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, sys.argv[1] + "/bench")
    sys.path.insert(0, sys.argv[1] + "/src")
    import jax
    from benchlib import cells, runner
    from repro.core import fleet
    bench = cells.Path(sys.argv[1]) / "bench"
    cell = cells.Cell(
        "fleet_mc_4chip", 4,
        cells.load_json(bench / "configs" / "fleet_world_mix.json"),
        dict(cells.load_json(bench / "traffic" / "draws_400k_4shard.json"),
             n_users=1024, checked=1),
        [{"name": "user_days_per_s", "unit": "user-days/s"},
         {"name": "setup_s", "unit": "s"}], [], bench)
    out = {}
    for name in ("sound", "no_exchange"):
        if name == "no_exchange":
            jax.lax.psum = lambda x, axis_name: x
            fleet._fleet_runner.cache_clear()
        line = runner.run(cell, seed=99, seconds=0.5, traced=False,
                          platform="cpu", out=io.StringIO(),
                          err=io.StringIO())
        out[name] = line["correct"]
    print(json.dumps(out))
""")


def test_fleet_exchange_between_chips_left_out():
    res = subprocess.run([sys.executable, "-c", EXCHANGE, str(ROOT)],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    import json
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}
