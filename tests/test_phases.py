"""Host phases and device scopes of the twin's query path.

`repro.core.phases.phase` spans each host phase in the profiler's trace
and counts it in `PHASE_STATS` (the ``phases`` tier of
`daysim.cache_stats()`); the fused day program names its five stages
with `jax.named_scope`.  These tests pin where each appears, how the
counters count, and that `WhatIf.ms` is each item's own latency."""
from __future__ import annotations

import dataclasses
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro.core import daysim, phases
from repro.serving.twin import DesignTwin

DT = 600.0
SCOPES = ("row_stage", "gather", "day_scan", "summary", "front")
BATCH_PHASES = ("repro.twin.batch", "repro.twin.group", "repro.daysim.push",
                "repro.daysim.dispatch", "repro.daysim.wait",
                "repro.daysim.fetch", "repro.daysim.report")


@pytest.fixture(scope="module")
def twin():
    return DesignTwin(platforms=("aria2_display",),
                      designs=daysim.DEFAULT_DESIGNS[:2],
                      schedules=("commuter",),
                      policies=("none", "thermal_governor"), dt_s=DT,
                      batch_window=2)


_trip = iter(np.arange(38.0, 44.0, 0.01))


def _fresh_policy() -> dict:
    """A value no earlier query used, so its assembly misses the cache."""
    gov = daysim.get_policy("thermal_governor")
    return {"policies": ("none", dataclasses.replace(
        gov, name="probe", temp_trip_c=float(next(_trip))))}


def _assembly():
    kw = daysim._batch_defaults()
    kw.update(platforms=("aria2_display",), designs=daysim.DEFAULT_DESIGNS[:2],
              schedules=("commuter",), policies=("none",), dt_s=DT)
    return daysim._assemble_query(**kw)


def _op_scopes(hlo: str) -> set:
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo):
        for part in path.split("/"):
            m = re.fullmatch(r"(?:\w+\()?(\w+)\)?", part)
            if m and m.group(1) in SCOPES:
                found.add(m.group(1))
    return found


@pytest.mark.parametrize("batched", [False, True])
def test_fused_programs_carry_the_five_scopes(batched):
    asm = _assembly()
    if batched:
        body = daysim._build_fused_batch(asm.plats, "xla")
        args = [jax.tree_util.tree_map(lambda x: np.stack([x, x]), t)
                for t in (asm.dyn, asm.ix)]
    else:
        body = daysim._build_fused(asm.plats, "xla")
        args = [asm.dyn, asm.ix]
    hlo = jax.jit(body).lower(*args).compile().as_text()
    assert _op_scopes(hlo) == set(SCOPES)


def test_profiled_run_nests_every_span_under_its_batch(twin, tmp_path):
    from jax.profiler import ProfileData
    twin.submit(**_fresh_policy())
    jax.profiler.start_trace(str(tmp_path))
    try:
        done = twin.run()
    finally:
        jax.profiler.stop_trace()
    assert len(done) == 1
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    spans = [(e.name.split("#", 1)[0], dict(e.stats), e.start_ns, e.end_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(phases.PREFIX)]
    names = {n for n, *_ in spans}
    assert names >= set(BATCH_PHASES) | {"repro.daysim.enumerate",
                                         "repro.daysim.assemble"}
    batch = [s for s in spans if s[0] == "repro.twin.batch"]
    assert len(batch) == 1
    _, args, b0, b1 = batch[0]
    assert args["items"] == 1 and "queued" in args
    for name, a, s, e in spans:
        assert str(a["batch"]) == str(args["batch"]), name
        assert b0 <= s <= e <= b1, name
        if name != "repro.twin.batch":
            assert a["parent"].startswith(phases.PREFIX), name


def test_one_call_per_phase_per_micro_batch(twin):
    for _ in range(4):
        twin.submit(**_fresh_policy())
    before = phases.snapshot()
    done = twin.run()
    after = phases.snapshot()
    assert len(done) == 4

    def calls(name):
        return (after[name]["calls"]
                - before.get(name, {"calls": 0})["calls"])
    for name in BATCH_PHASES:           # batch_window 2: two micro-batches
        assert calls(name) == 2, name
    assert calls("repro.daysim.assemble") == 4      # one miss per item
    assert calls("repro.daysim.enumerate") == 8     # group pass + batch
    for name, st in after.items():
        b = before.get(name, {"calls": 0, "hist": {}})
        dh = sum(st["hist"].values()) - sum(b["hist"].values())
        assert dh == st["calls"] - b["calls"], name
    # self time leaves out the phases nested inside: the group pass holds
    # the first assembly of each item
    g, a = after["repro.twin.group"], after["repro.daysim.assemble"]
    assert g["self_ns"] < g["total_ns"]
    assert a["self_ns"] == a["total_ns"]


def test_histogram_buckets_and_snapshots_difference():
    name = phases.PREFIX + "test.histogram"
    before = phases.snapshot()
    for ns in (0, 999, 1000, 3000, 3999, 4000, 2_500_000):
        phases.record(name, ns)
    snap = phases.snapshot()
    phases.record(name, 1000)           # after the snapshot: not in it
    st = snap[name]
    assert st["hist"] == {0: 2, 1: 1, 2: 2, 3: 1, 12: 1}
    assert st["calls"] - before.get(name, {"calls": 0})["calls"] == 7
    assert st["total_ns"] == 2_512_998
    assert phases.snapshot()[name]["hist"][1] == 2


def test_nested_phase_self_time_and_inherited_batch():
    outer, inner = "test.outer", "test.inner"
    with phases.phase(outer, batch=41) as o:
        with phases.phase(inner) as i:
            time.sleep(0.002)
        assert i.batch == 41
    st = phases.snapshot()
    so, si = st[phases.PREFIX + outer], st[phases.PREFIX + inner]
    assert o.child_ns == si["total_ns"] >= 2_000_000
    assert so["self_ns"] == so["total_ns"] - si["total_ns"]


def test_backend_compiles_are_counted():
    before = phases.snapshot().get("jax.backend_compile", {"calls": 0})
    c = float(time.perf_counter_ns() % 997)  # a program no cache holds
    jax.jit(lambda x: x * c + 1.0).lower(np.ones(3, np.float32)).compile()
    after = phases.snapshot()["jax.backend_compile"]
    assert after["calls"] >= before["calls"] + 1
    assert after["total_ns"] > 0


def test_whatif_ms_is_each_items_own_latency(twin):
    for _ in range(3):                  # batch_window 2: batches of 2, 1
        twin.submit(**_fresh_policy())
    t_submit_last = time.perf_counter()
    done = twin.run()
    assert len(done) == 3
    for wi in done:
        assert wi.submitted_s <= t_submit_last < wi.finished_s
        assert wi.ms == pytest.approx(
            (wi.finished_s - wi.submitted_s) * 1e3)
    # the first two finish together, so their latencies differ by just
    # their submit times (a batch mean would make them equal); the third
    # waits for them
    assert done[0].finished_s == done[1].finished_s < done[2].finished_s
    assert done[0].ms - done[1].ms == pytest.approx(
        (done[1].submitted_s - done[0].submitted_s) * 1e3)
    assert done[0].ms > done[1].ms
