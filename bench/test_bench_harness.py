"""Tests of the benchmark harness that need no accelerator: the trace
reduction, the work counts, the open-loop schedule, discovery by name,
and the names and units of `BENCHMARK.json`."""
from __future__ import annotations

import contextlib
import json
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import cells, openloop, trace  # noqa: E402
from benchlib.trace import Event  # noqa: E402


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _synthetic():
    ev = [Event(HOST, "python", "bench:window", 0, 100),
          Event(HOST, "python", "bench:run", 5, 50),
          Event(HOST, "python", "bench:run", 50, 90),
          Event(HOST, "python", "not_a_span", 0, 100),
          Event(DEV, "XLA Modules", "jit_fused_batch(1)", 20, 45),
          Event(DEV, "XLA Modules", "jit_run(7)", 70, 85),
          Event(DEV, "XLA Ops", "while.3", 20, 40),
          Event(DEV, "XLA Ops", "fusion.1", 40, 45),
          Event(DEV, "XLA Ops", "all-reduce.1", 70, 72),
          Event(DEV, "XLA Ops", "fusion.2", 72, 85),
          Event(DEV, "XLA Ops", "fusion.2", 110, 120)]   # after the window
    return ev


def test_trace_busy_union_and_idle_share():
    red = trace.reduce(_synthetic())
    assert red["devices"] == [DEV]
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["busy_union"][DEV] == [[20, 45], [70, 85]]
    assert trace.busy_within(red, 5, 50) == pytest.approx(25e-9)


def test_trace_time_by_module_and_op():
    red = trace.reduce(_synthetic())
    assert red["by_module"] == pytest.approx(
        {"jit_fused_batch": 25e-9, "jit_run": 15e-9})
    assert red["ops_in_module"]["jit_fused_batch"] == pytest.approx(
        {"while.3": 20e-9, "fusion.1": 5e-9})
    assert red["ops_in_module"]["jit_run"]["fusion.2"] == pytest.approx(13e-9)
    assert red["collective_s"] == pytest.approx(2e-9)
    assert red["by_op"]["fusion.2"] == pytest.approx(13e-9)


def test_trace_idle_gaps_labelled_by_host_span():
    red = trace.reduce(_synthetic())
    assert red["idle_by_span"] == pytest.approx(
        {"run": 45e-9, "outside_spans": 15e-9})
    assert trace.top(red["idle_by_span"], 1) == [["run", pytest.approx(45e-9)]]


def test_trace_two_devices_average():
    ev = _synthetic() + [Event("/device:TPU:1", "XLA Ops", "fusion.9", 0, 10)]
    red = trace.reduce(ev)
    assert red["busy_s"] == pytest.approx((40e-9 + 10e-9) / 2)


def test_layer_helpers_on_synthetic_trace():
    from benchlib import layers
    ctx = {"trace": trace.reduce(_synthetic())}
    assert layers.loop_time(ctx, "fused") == pytest.approx(20e-9)
    assert layers.largest_program(ctx, "jit_run") == pytest.approx(15e-9)
    assert layers.idle_pct(ctx) == pytest.approx(60.0)
    host, dev = layers.span_self_device(ctx, "run")
    assert host == pytest.approx(85e-9)
    assert dev == pytest.approx(40e-9)


def test_device_metrics_count_only_the_traced_part():
    from benchlib import layers
    done = [{"i": i, "req": {"due": 0.5 * i}} for i in range(6)]
    ctx = {"spans": [("run", 0.0, 1.0), ("run", 1.0, 2.0), ("run", 2.0, 3.0)],
           "traced_from_s": 1.0, "traced_until_s": 3.0,
           "window": {"batches": [(0.0, 1.0, 1), (1.0, 2.0, 2), (2.0, 3.0, 3)],
                      "done": done}}
    assert layers.traced_count(ctx, "run") == 2
    assert layers.traced_queries(ctx) == done[1:]
    assert layers.untraced_queries(ctx) == done[:2]
    whole = dict(ctx, traced_from_s=None, traced_until_s=None)
    assert layers.traced_count(whole, "run") == 3
    assert cells.module("metrics", "batch_items_mean.twin").read(ctx) == 1.0


# ---------------------------------------------------------------------------
# work counts against hand counts
# ---------------------------------------------------------------------------

def test_day_scan_work_by_hand():
    w = cells.module("work", "day_scan").work(combos=2, steps=3, levels=2)
    assert w["flops"] == 110 * 2 * 3
    # per combo: 3 steps x (3 tables x 2 levels + 5 columns) x 4 bytes,
    # plus a 12-number summary
    assert w["bytes"] == 2 * (3 * 11 * 4 + 48)


def test_fleet_scan_work_by_hand():
    w = cells.module("work", "fleet_scan").work(
        users=10, steps=4, days=1, archetypes=2, levels=3, streams=4)
    assert w["flops"] == 142 * 10 * 4
    assert w["bytes"] == 10 * 27 * 4 + 4 * 2 * (3 * 7 + 5) * 4


# ---------------------------------------------------------------------------
# the open-loop schedule
# ---------------------------------------------------------------------------

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 20.0},
       "tenants": 3, "zipf_s": 1.1, "repeat_share": 0.25,
       "small_share": 0.5,
       "perturb": [{"target": "a", "key": "x", "plus_minus": 1.0},
                   {"target": "*batteries", "key": "c", "rel": 0.1}]}


def test_schedule_same_seed_same_requests():
    a = openloop.schedule(MIX, 5.0, 2**33 + 7)
    b = openloop.schedule(MIX, 5.0, 2**33 + 7)
    assert a == b
    c = openloop.schedule(MIX, 5.0, 8)
    assert [r["values"] for r in a] != [r["values"] for r in c]


def test_schedule_every_seed_has_the_same_arrivals_and_kinds():
    a = openloop.schedule(MIX, 5.0, 1)
    b = openloop.schedule(MIX, 5.0, 2)
    assert len(a) == len(b) == 100
    key = ("due", "tenant", "small", "repeat")
    assert [[r[k] for k in key] for r in a] == [[r[k] for k in key]
                                                  for r in b]
    gaps = np.diff([0.0] + [r["due"] for r in a])
    q = -np.log1p(-(np.arange(100) + 0.5) / 100)
    assert np.allclose(np.sort(gaps), np.sort(q) * 5.0 / q.sum())
    assert 0.0 < a[0]["due"] and a[-1]["due"] == pytest.approx(5.0)
    other = openloop.schedule(dict(MIX, pattern_seed=3), 5.0, 1)
    assert [r["due"] for r in other] != [r["due"] for r in a]


def test_bursts_share_sizes_and_stay_in_window():
    mix = dict(MIX, arrivals={"kind": "bursts", "burst_rate_per_s": 2.0,
                              "burst_min": 4, "burst_max": 16,
                              "burst_spread_s": 0.1})
    a = openloop.schedule(mix, 10.0, 3)
    b = openloop.schedule(mix, 10.0, 4)
    assert len(a) == len(b) == sum(4 + i % 13 for i in range(20))
    assert all(0.0 < r["due"] <= 10.0 for r in a)


def test_repeats_copy_the_tenants_previous_values():
    reqs = openloop.schedule(MIX, 5.0, 11)
    last = {}
    for r in reqs:
        if r["repeat"]:
            assert r["values"] == last[r["tenant"]]["values"]
        last[r["tenant"]] = r


class _FakeTwin:
    """Admission queue whose `run()` takes a fixed time."""

    def __init__(self, secs):
        self.queue, self.batch_window, self.secs, self._q = [], 4, secs, 0

    def submit(self, **kw):
        self._q += 1

        class W:
            pass
        w = W()
        w.qid, w.report = self._q, None
        self.queue.append(w)
        return w.qid

    def run(self, max_steps):
        batch = self.queue[:max_steps]
        del self.queue[:len(batch)]
        time.sleep(self.secs)
        return batch


class _Stats:
    @staticmethod
    def cache_stats():
        return {"exec": {"traces": 0}, "assemblies": {"hits": 0}}


def test_latency_is_timed_from_the_due_time(monkeypatch):
    twin_adp = cells.module("adapters", "twin")
    monkeypatch.setattr(twin_adp, "_overrides", lambda st, req: {})
    st = twin_adp.State()
    st.mix = {"arrivals": {"kind": "poisson", "rate_per_s": 40.0}}
    st.seed, st.twin, st.daysim = 5, _FakeTwin(0.05), _Stats
    w = twin_adp.window(st, 1.0, lambda name: contextlib.nullcontext())
    assert w["attempted"] == 40 and w["failed"] == 0
    assert len(w["done"]) == 40
    for d in w["done"]:
        assert d["wait_s"] >= 0.0
        assert d["latency_s"] >= d["wait_s"] + 0.05 - 1e-3
    # a server slower than the arrivals: later requests wait longer
    assert w["done"][-1]["wait_s"] > w["done"][0]["wait_s"]


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------

def _tree_bytes(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_files_are_found_without_editing_existing_ones(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    before = _tree_bytes(tmp_path / "bench")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "twin_default_grid.json").read_text())
    cfg["name"] = "twin_new_grid"
    (tmp_path / "bench" / "configs" / "twin_new_grid.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "whatif_new.json").write_text(
        json.dumps(dict(MIX, small_platforms=[], checked=1)))
    (tmp_path / "bench" / "metrics" / "new_metric.twin.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append({"name": "twin_new_grid", "source": "x",
                         "file": "bench/configs/twin_new_grid.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "twin_new", "config": "twin_new_grid",
                           "traffic": "whatif_new", "chips": 1, "why": "x"})
    b["end_to_end"][0]["workloads"].append("twin_new")
    b["per_layer"].append({"name": "new_metric.twin", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "twin admission queue",
                           "moves": b["end_to_end"][0]["name"],
                           "workloads": ["twin_new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = cells.load(tmp_path, "twin_new")
    assert cell.config["name"] == "twin_new_grid"
    assert cell.traffic["arrivals"] == MIX["arrivals"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric.twin"]
    mod = cells.module("metrics", "new_metric.twin", cell.bench_dir)
    assert mod.read({}) == 42.0
    assert cells.module("adapters", cell.config["adapter"],
                        cell.bench_dir).setup
    after = _tree_bytes(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        cells.peaks("TPU v0 imaginary")
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract's names, units and cross-references
# ---------------------------------------------------------------------------

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert 1 <= len(B["paths"]) <= 16
    assert all(re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
               and not p.startswith("/") and ".." not in p
               for p in B["paths"])
    assert len(B["command"]) <= 32
    assert all(LINE.match(w) for w in B["command"])


def test_names_units_and_lines():
    items = B["configs"] + B["workloads"] + B["end_to_end"] + B["per_layer"]
    for it in items:
        assert NAME.match(it["name"]), it["name"]
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in B["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
    for c in B["configs"]:
        assert LINE.match(c["why"]) and LINE.match(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
    for m in B["per_layer"]:
        assert LINE.match(m["layer"])
    for group in ("configs", "workloads"):
        names = [x["name"] for x in B[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_entries_have_only_the_contract_keys():
    assert all(set(c) == {"name", "source", "file", "reduced", "why"}
               for c in B["configs"])
    assert all(set(w) == {"name", "config", "traffic", "chips", "why"}
               for w in B["workloads"])
    assert all(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"} for m in B["end_to_end"])
    assert all(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
               for m in B["per_layer"])


def test_cross_references_resolve_to_files():
    cells_by_name = {w["name"]: w for w in B["workloads"]}
    confs = {c["name"]: c for c in B["configs"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in B["end_to_end"])
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in B["end_to_end"])
    for c in B["configs"]:
        assert c["file"].startswith("bench/")
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in B["workloads"])
        d = json.loads((ROOT / c["file"]).read_text())
        assert (BENCH / "adapters" / f"{d['adapter']}.py").is_file()
    for w in B["workloads"]:
        assert w["config"] in confs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in B["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in cells_by_name
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for w in B["workloads"]:
        reported = [m for m in B["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in B["per_layer"])
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_command_needs_no_file_outside_paths():
    script = B["command"][1]
    assert any(script.startswith(p + "/") for p in B["paths"])
    assert (ROOT / script).is_file()
