"""Twin v2 serving contracts: batched queries, canonical shape
bucketing, the host assembly's skeleton cache, the persistent-compile-
cache shim, and the cache-stats accessor.

The load-bearing invariants:
  * batched (`what_if_many` / `day_pareto_batch`) answers are
    BIT-identical to serial `query`/`what_if` answers — front masks,
    survival flags, every objective;
  * bucket padding is invisible: reports carry only the real rows, and
    axis sizes inside one bucket reuse the warm executable
    (`EXEC_STATS["traces"]` flat);
  * concurrent threads hammering `submit()`/`run()` with mixed shapes
    serialize to the same results as serial queries, with no retraces
    once the shapes are warm;
  * a what-if that reuses a cached skeleton packs the same bytes, and
    answers the same report, as a cold build of it; a structural change
    never reuses one.
"""
from __future__ import annotations

import dataclasses
import threading
from pathlib import Path

import jax.tree_util as jax_tree
import numpy as np
import pytest

from repro import compat
from repro.core import daysim, dse, scenarios
from repro.serving.engine import drain_microbatched
from repro.serving.twin import DesignTwin

DT = 60.0

_FIELDS = ("time_to_empty_h", "peak_skin_c", "pod_hours", "end_soc",
           "energy_mwh", "throttled_h", "steady_mw", "day_hours")


def _point_whatifs(k: int, start: int = 0) -> list:
    gov = daysim.get_policy("thermal_governor")
    return [{"platform": "aria2_display",
             "design": daysim.DEFAULT_DESIGNS[1],
             "schedule": "commuter",
             "policy": dataclasses.replace(
                 gov, name=f"t{start + i}",
                 temp_trip_c=38.0 + 0.05 * (start + i))}
            for i in range(k)]


def _policies(k: int, start: int = 0) -> tuple:
    gov = daysim.get_policy("thermal_governor")
    return tuple(dataclasses.replace(gov, name=f"v{start + i}",
                                     temp_trip_c=38.0 + 0.1 * (start + i))
                 for i in range(k))


def _assert_identical(a, b):
    assert a.combos == b.combos
    assert np.array_equal(a.front_mask, b.front_mask)
    assert np.array_equal(a.survives(), b.survives())
    for f in _FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f


@pytest.fixture(scope="module")
def twin():
    return DesignTwin(platforms=("aria2_display",),
                      designs=daysim.DEFAULT_DESIGNS[:2],
                      schedules=("commuter",), dt_s=DT)


# -- bucketing primitives --------------------------------------------------

def test_bucket_size():
    assert [daysim.bucket_size(n) for n in (1, 2, 3, 5, 8, 9, 63, 64)] \
        == [1, 2, 4, 8, 8, 16, 64, 64]
    with pytest.raises(ValueError):
        daysim.bucket_size(0)


def test_scenarioset_pad():
    sset = scenarios.ScenarioSet.build(
        [{"on_device": ("asr",), "compression": 8.0, "name": "a"},
         {"on_device": (), "compression": 16.0, "name": "b"},
         {"on_device": (), "compression": 4.0, "name": "c"}])
    padded = sset.pad(8)
    assert len(padded) == 8
    assert padded.names == ("a", "b", "c", "", "", "", "", "")
    # clone rows repeat row 0 exactly
    assert np.array_equal(padded.placement[3:], np.repeat(
        sset.placement[:1], 5, axis=0))
    assert np.array_equal(padded.compression[:3], sset.compression)
    assert sset.pad(3) is sset
    with pytest.raises(ValueError):
        sset.pad(2)


def test_report_carries_only_real_rows(twin):
    rep = twin.query()
    n = len(rep.combos)
    assert daysim.bucket_size(n) > n    # padding actually happened
    for f in _FIELDS:
        assert getattr(rep, f).shape[0] == n
    assert rep.front_mask.shape[0] == n


# -- batched queries -------------------------------------------------------

def test_batch_bit_identical_to_serial(twin):
    whatifs = _point_whatifs(5)         # K=5 -> bucket 8: pad exercised
    serial = [twin.what_if(**w) for w in whatifs]
    batch = twin.what_if_many(whatifs)
    assert len(batch) == 5
    for s, b in zip(serial, batch):
        _assert_identical(s, b)


def test_batch_grid_queries_bit_identical(twin):
    queries = [{"policies": _policies(2, 10 * i)} for i in range(3)]
    serial = [twin.query(**q) for q in queries]
    batch = twin.query_batch(queries)
    for s, b in zip(serial, batch):
        _assert_identical(s, b)


def test_varied_k_batches_zero_retrace(twin):
    twin.what_if_many(_point_whatifs(8, 50))      # warm the K-bucket 8
    before = daysim.EXEC_STATS["traces"]
    for k in (5, 6, 7, 8):                        # fresh values each
        out = twin.what_if_many(_point_whatifs(k, 100 + 10 * k))
        assert len(out) == k
    assert daysim.EXEC_STATS["traces"] == before


def test_varied_n_grids_zero_retrace(twin):
    # 5- and 6-policy grids share one bucketed signature (combos 10/12
    # -> bucket 16, rows -> bucket 256): sizes differ, executable warm
    twin.query(policies=_policies(6))
    before = daysim.EXEC_STATS["traces"]
    r5 = twin.query(policies=_policies(5, 20))
    r6 = twin.query(policies=_policies(6, 40))
    assert daysim.EXEC_STATS["traces"] == before
    assert len(r5.combos) == 10 and len(r6.combos) == 12


def test_batch_mixed_signature_raises(twin):
    with pytest.raises(ValueError, match="different bucketed shape"):
        dse.day_pareto_batch(
            [{"policies": _policies(2)}, {"policies": _policies(6)}],
            platforms=("aria2_display",),
            designs=daysim.DEFAULT_DESIGNS[:2],
            schedules=("commuter",), dt_s=DT)


def test_batch_rejects_pallas_and_empty():
    with pytest.raises(ValueError, match="backend"):
        daysim.day_grid_batch([{}], backend="pallas")
    with pytest.raises(ValueError, match="at least one"):
        daysim.day_grid_batch([])


# -- admission queue / concurrency ----------------------------------------

def test_drain_microbatched_window_and_budget():
    queue = list(range(10))
    seen = []

    def eval_batch(batch):
        seen.append(list(batch))
        return batch

    out = drain_microbatched(queue, 4, eval_batch, max_items=7)
    assert out == list(range(7))
    assert seen == [[0, 1, 2, 3], [4, 5, 6]]
    assert queue == [7, 8, 9]
    assert drain_microbatched(queue, 4, eval_batch) == [7, 8, 9]
    assert queue == []


def test_run_microbatches_and_fans_out(twin):
    whatifs = _point_whatifs(5, 200)
    serial = [twin.what_if(**w) for w in whatifs]
    qids = [twin.submit(**w) for w in whatifs]
    batches_before = twin.stats.batches
    done = twin.run()
    assert [wi.qid for wi in done] == qids
    assert twin.queue == []
    assert twin.stats.batches == batches_before + 1   # one sig group
    for s, wi in zip(serial, done):
        _assert_identical(s, wi.report)


def test_concurrent_submit_run_mixed_shapes(twin):
    whatifs = _point_whatifs(6, 300)
    grids = [{"policies": _policies(2, 300 + 10 * i)} for i in range(4)]
    serial = {f"p{i}": twin.what_if(**w) for i, w in enumerate(whatifs)}
    serial.update({f"g{i}": twin.query(**q)
                   for i, q in enumerate(grids)})
    twin.what_if_many(whatifs)                  # warm both batch shapes
    twin.query_batch(grids)

    before = daysim.EXEC_STATS["traces"]
    qid_to_key, results, errors = {}, {}, []

    def submit_points(lo, hi):
        for i in range(lo, hi):
            qid_to_key[twin.submit(**whatifs[i])] = f"p{i}"

    def submit_grids():
        for i, q in enumerate(grids):
            qid_to_key[twin.submit(**q)] = f"g{i}"

    def drain():
        try:
            for wi in twin.run():
                results[wi.qid] = wi.report
        except Exception as e:                  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submit_points, args=(0, 3)),
               threading.Thread(target=submit_points, args=(3, 6)),
               threading.Thread(target=submit_grids),
               threading.Thread(target=drain),
               threading.Thread(target=drain)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results.update({wi.qid: wi.report for wi in twin.run()})

    assert not errors
    assert len(results) == len(qid_to_key) == 10
    for qid, key in qid_to_key.items():
        _assert_identical(serial[key], results[qid])
    assert daysim.EXEC_STATS["traces"] == before, \
        "concurrent warm serving retraced"


# -- cache tiers -----------------------------------------------------------

def test_cache_stats_accessor(twin):
    stats = daysim.cache_stats()
    assert set(stats) == {"rows", "assemblies", "skeletons", "pipelines",
                          "exec", "phases", "transfers"}
    for name, tier in stats.items():
        if name not in ("phases", "transfers"):
            assert {"hits", "misses", "size"} <= set(tier)
    assert set(stats["transfers"]) == {"h2d_calls", "h2d_bytes",
                                       "d2h_calls", "d2h_bytes"}
    a0 = stats["assemblies"]["hits"]
    p0 = stats["pipelines"]["hits"]
    d0 = stats["phases"].get("repro.daysim.dispatch", {"calls": 0})["calls"]
    twin.query()
    twin.query()                        # identical: every tier hits
    stats = daysim.cache_stats()
    assert stats["assemblies"]["hits"] >= a0 + 2
    assert stats["pipelines"]["hits"] >= p0 + 2
    assert stats["phases"]["repro.daysim.dispatch"]["calls"] >= d0 + 2
    assert stats["exec"]["size"] >= 1
    assert stats["rows"]["evictions"] >= 0
    assert set(stats["skeletons"]) == {"hits", "misses", "evictions", "size"}
    assert 1 <= stats["skeletons"]["size"] <= daysim._SKELETONS_MAX


# -- skeleton cache: structure built once, values written per query -------

SK_DT = DT
SK_GOV = daysim.get_policy("thermal_governor")
SK_BASE = {**daysim._batch_defaults(),
           "platforms": ("aria2_display", "aria2_puck_split"),
           "designs": daysim.DEFAULT_DESIGNS[:2],
           # a docked day (charge split between glasses and puck) and a
           # shorter one (padded steps)
           "schedules": ("commuter_dock", "desk_day"),
           "policies": ("none", SK_GOV), "dt_s": SK_DT,
           "theta": {"eff_scale": 1.0}}


def _battery(name: str, scale: float) -> daysim.BatterySpec:
    bat = daysim.battery_for(name)
    return dataclasses.replace(bat, capacity_mwh=bat.capacity_mwh * scale)


def _puck_variant():
    from repro.core import platform as registry
    plat = registry.get("aria2_puck_split")
    comp = tuple((k, v * 1.2 if k == "base_mw" else v)
                 for k, v in plat.companion)
    return dataclasses.replace(plat, companion=comp)


# value-only changes: each reuses the skeleton `SK_BASE` built
SK_VALUES = {
    "trip_points": lambda: {"policies": ("none", dataclasses.replace(
        SK_GOV, name="hot", temp_trip_c=41.25, soc_trip=0.09))},
    # glasses capacity moves, the puck's does not: the charge split too
    "capacity": lambda: {"battery": {
        "aria2_display": _battery("aria2_display", 1.1),
        "aria2_puck_split": _battery("aria2_puck_split", 0.85)}},
    "theta": lambda: {"theta": {"eff_scale": 1.05}},
    "n_users": lambda: {"n_users": 2.5e6},
    "standby_mw": lambda: {"standby_mw": 52.0},
}

# structural changes: each must build a skeleton of its own
SK_STRUCTURE = {
    "compression": lambda: {"designs": (
        dict(daysim.DEFAULT_DESIGNS[0], compression=24.0),
        daysim.DEFAULT_DESIGNS[1])},
    "actions": lambda: {"policies": ("none", dataclasses.replace(
        SK_GOV, actions=(dataclasses.replace(SK_GOV.actions[0],
                                             fps_mult=3.0),)
        + SK_GOV.actions[1:]))},
    "schedule": lambda: {"schedules": (
        daysim.get_schedule("commuter_dock").with_ambient_offset(3.0),
        "desk_day")},
    "dt_s": lambda: {"dt_s": SK_DT / 2},
    "platform": lambda: {"platforms": ("aria2_display", _puck_variant())},
}


def _clear_host_tiers():
    """Forget every host-built assembly, skeleton and pipeline; the
    compiled programs stay."""
    for tier in (daysim._ASSEMBLIES, daysim._SKELETONS, daysim._PIPELINES):
        tier.clear()


def _skeleton_counts() -> tuple:
    sk = daysim.cache_stats()["skeletons"]
    return sk["hits"], sk["misses"]


def _same_bytes(a, b) -> bool:
    return all(x.dtype == y.dtype and x.tobytes() == y.tobytes()
               for x, y in zip(a, b)) and len(a) == len(b)


def _assert_same_assembly(got, want):
    assert got.sig == want.sig and got.n_real == want.n_real
    for g, w in zip(got.packed, want.packed):
        assert _same_bytes(g, w)
    for g, w in zip((got.dyn, got.ix), (want.dyn, want.ix)):
        g_l, g_def = jax_tree.tree_flatten(g)
        w_l, w_def = jax_tree.tree_flatten(w)
        assert g_def == w_def
        assert _same_bytes([np.asarray(x) for x in g_l],
                           [np.asarray(x) for x in w_l])


@pytest.mark.parametrize("change", list(SK_VALUES))
def test_skeleton_hit_is_bit_identical_to_a_cold_build(change):
    query = {**SK_BASE, **SK_VALUES[change]()}
    _clear_host_tiers()
    base = daysim._assemble_query(**SK_BASE)
    h0, m0 = _skeleton_counts()
    hit = daysim._assemble_query(**query)
    assert _skeleton_counts() == (h0 + 1, m0)
    # the value really changed what is packed
    assert not _same_bytes(hit.packed[0] + hit.packed[1],
                           base.packed[0] + base.packed[1])
    hit_rep = daysim.day_grid_batch([query])[0]
    _clear_host_tiers()
    cold = daysim._assemble_query(**query)
    assert _skeleton_counts() == (h0 + 1, m0 + 1)
    cold_rep = daysim.day_grid_batch([query])[0]
    _assert_same_assembly(hit, cold)
    _assert_identical(hit_rep, cold_rep)
    for f in ("end_soc_puck", "peak_skin_puck_c", "shutdown"):
        assert np.array_equal(getattr(hit_rep, f), getattr(cold_rep, f))


@pytest.mark.parametrize("change", list(SK_STRUCTURE))
def test_structural_change_misses_and_builds_its_own_rows(change):
    query = {**SK_BASE, **SK_STRUCTURE[change]()}
    _clear_host_tiers()
    base = daysim._assemble_query(**SK_BASE)
    h0, m0 = _skeleton_counts()
    warm = daysim._assemble_query(**query)
    assert _skeleton_counts() == (h0, m0 + 1)
    assert daysim.cache_stats()["skeletons"]["size"] == 2
    if warm.sig == base.sig:
        # same shapes: a stale skeleton would have packed base's bytes
        assert not _same_bytes(warm.packed[0] + warm.packed[1],
                               base.packed[0] + base.packed[1])
    _clear_host_tiers()
    _assert_same_assembly(warm, daysim._assemble_query(**query))


def test_skeleton_hit_pads_lanes_with_lane_zero():
    grid = {**SK_BASE, "platforms": ("aria2_puck_split",),
            "policies": daysim.DEFAULT_POLICIES}      # 12 combos: 16 lanes
    _clear_host_tiers()
    daysim._assemble_query(**grid)
    h0, m0 = _skeleton_counts()
    asm = daysim._assemble_query(**{**grid, **SK_VALUES["capacity"](),
                                    **SK_VALUES["standby_mw"]()})
    assert _skeleton_counts() == (h0 + 1, m0)
    n, n_b = asm.n_real, daysim.bucket_size(asm.n_real)
    assert n < n_b
    lanes = [*asm.dyn["const"].values(), asm.dyn["act_mult"],
             *asm.ix.values()]
    for leaf in lanes:
        assert leaf.shape[0] == n_b
        assert np.array_equal(leaf[n:], np.repeat(leaf[:1], n_b - n, 0))


def test_skeleton_counters_fifo_bound_and_clear(monkeypatch):
    monkeypatch.setattr(daysim, "_SKELETONS_MAX", 3)
    _clear_host_tiers()
    sk0 = daysim.cache_stats()["skeletons"]
    grid = {**SK_BASE, "platforms": ("aria2_display",),
            "schedules": ("desk_day",), "policies": ("none",)}

    def structure(i: int) -> dict:
        return {**grid, "designs": (dict(daysim.DEFAULT_DESIGNS[0],
                                         compression=8.0 + i),)}

    for i in range(5):                  # five structures, three kept
        daysim._assemble_query(**structure(i))
    daysim._assemble_query(**{**structure(4), "n_users": 3e6})  # hit
    daysim._assemble_query(**{**structure(0), "n_users": 3e6})  # evicted
    sk = daysim.cache_stats()["skeletons"]
    assert {k: sk[k] - sk0[k] for k in ("hits", "misses", "evictions")} \
        == {"hits": 1, "misses": 6, "evictions": 3}
    assert sk["size"] == len(daysim._SKELETONS) == 3
    daysim.clear_exec_cache()
    assert daysim.cache_stats()["skeletons"] == {
        "hits": 0, "misses": 0, "evictions": 0, "size": 0}


def test_shared_skeleton_buffers_are_read_only():
    _clear_host_tiers()
    a = daysim._assemble_query(**SK_BASE)
    b = daysim._assemble_query(**{**SK_BASE, "n_users": 4e6})
    skel = next(iter(daysim._SKELETONS.values()))
    for buf in skel.packed[0] + skel.packed[1]:
        assert not buf.flags.writeable
    assert not skel.seg_charge.flags.writeable
    # the int32 buffers carry no value: both assemblies share them, and
    # neither can write them
    for i, j in ((0, 1), (1, 1)):
        assert a.packed[i][j].dtype == np.int32
        assert a.packed[i][j] is b.packed[i][j] is skel.packed[i][j]
    with pytest.raises(ValueError):
        a.ix["seg_of"][0, 0] = 1
    with pytest.raises(ValueError):
        a.packed[1][1][:] = 0
    # the float32 buffers are each assembly's own copy
    assert a.packed[1][0].dtype == np.float32
    assert not np.shares_memory(a.packed[1][0], b.packed[1][0])
    assert not np.shares_memory(a.packed[1][0], skel.packed[1][0])
    before = skel.packed[1][0].tobytes()
    a.ix["ambient"][0, 0] += 1.0
    assert skel.packed[1][0].tobytes() == before
    assert b.ix["ambient"][0, 0] != a.ix["ambient"][0, 0]


def test_persistent_cache_shim(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR`, when set, is the cache directory and
    no other is configured in code; unset, the cache lives at the fixed
    version-keyed path inside the checkout."""
    import jax
    prev_dir = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compat.compile_cache_dir() == tmp_path
        assert compat.enable_persistent_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == prev_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        out = compat.enable_persistent_cache()
        assert out == (Path(__file__).resolve().parents[1] / "results"
                       / "compile_cache" / f"jax-{jax.__version__}")
        assert out.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(out)
        # idempotent: a second call configures the same directory
        assert compat.enable_persistent_cache() == out
    finally:
        if prev_dir is not None:
            jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_measured_flops_disk_cache(monkeypatch, tmp_path):
    """The measured-FLOPs table is derived on the host CPU backend and
    neither read from nor written to any cache directory: a stale table
    lying in the compile cache cannot change the wearable model."""
    import json
    from repro.perception import nets
    want = nets.measured_flops()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    fake = {k: float(i + 1) for i, k in enumerate(want)}
    (tmp_path / "measured_flops.json").write_text(json.dumps(fake))
    nets.measured_flops.cache_clear()
    try:
        assert nets.measured_flops() == want
    finally:
        nets.measured_flops.cache_clear()
    assert [p.name for p in tmp_path.iterdir()] == ["measured_flops.json"]
