"""PlatformSpec / ScenarioSet batch API: parity with the legacy oracle,
registry round-trip, vmap-vs-loop equivalence, new knobs, SKU variants,
and the offload fleet fallback path."""
import json

import numpy as np
import pytest

from repro.core import aria2, offload, scenarios
from repro.core import platform as platform_registry
from repro.core.aria2 import PRIMITIVES, Scenario
from repro.core.platform import PlatformSpec
from repro.core.scenarios import ScenarioSet, all_placements


@pytest.fixture(scope="module")
def plat():
    return aria2.aria2_platform()


# ---------------------------------------------------------------------------
# batch == legacy per-scenario implementation
# ---------------------------------------------------------------------------

def test_batch_matches_legacy_total_mw(plat):
    """Batched vmap totals match the seed dict implementation to 1e-6."""
    scs = [Scenario("t", s, compression=c, fps_scale=f)
           for s in all_placements()
           for c in (1.0, 10.0, 40.0) for f in (1.0, 4.0)]
    sset = ScenarioSet.from_scenarios(scs)
    batch = np.asarray(scenarios.total_mw(plat, sset))
    legacy = np.array([float(aria2.legacy_total_mw(sc)) for sc in scs])
    np.testing.assert_allclose(batch, legacy, rtol=1e-6)
    mbatch = np.asarray(scenarios.offloaded_mbps(plat, sset))
    mlegacy = np.array([aria2.legacy_offloaded_mbps(sc) for sc in scs])
    np.testing.assert_allclose(mbatch, mlegacy, rtol=1e-6)


def test_component_loads_match_legacy(plat):
    """Per-component engine loads equal the seed dict, name by name."""
    sc = Scenario("t", ("vio", "asr"), compression=8.0, fps_scale=2.0)
    new, _ = aria2.component_loads(sc)
    legacy, _ = aria2.legacy_component_loads(sc)
    assert set(new) == set(legacy)
    for name in legacy:
        np.testing.assert_allclose(float(new[name]), float(legacy[name]),
                                   rtol=1e-5, err_msg=name)


def test_vmap_equals_loop_over_full_grid(plat):
    """One batched call == per-scenario wrapper loop over the >=768-point
    placement x compression x fps grid."""
    sset = ScenarioSet.grid()
    assert len(sset) >= 768
    batch = np.asarray(scenarios.total_mw(plat, sset))
    assert batch.shape == (len(sset),)
    idx = list(range(0, len(sset), 37))       # loop a stratified subset
    for i in idx:
        sc = Scenario("t", sset.on_device(i),
                      compression=float(sset.compression[i]),
                      fps_scale=float(sset.fps_scale[i]))
        np.testing.assert_allclose(batch[i], float(aria2.total_mw(sc)),
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# registry + serialization
# ---------------------------------------------------------------------------

def test_platform_roundtrip_serialization(plat):
    rebuilt = PlatformSpec.from_dict(json.loads(json.dumps(plat.to_dict())))
    assert rebuilt == plat
    sset = ScenarioSet.grid(placements=((), tuple(PRIMITIVES)),
                            compressions=(10.0,), fps_scales=(1.0,))
    np.testing.assert_array_equal(
        np.asarray(scenarios.total_mw(rebuilt, sset)),
        np.asarray(scenarios.total_mw(plat, sset)))


def test_hash_agrees_with_equality(plat):
    """Equal specs hash equal; a spec that differs only in its components,
    under the same name, is still a key of its own."""
    import dataclasses
    rebuilt = PlatformSpec.from_dict(json.loads(json.dumps(plat.to_dict())))
    assert rebuilt is not plat and hash(rebuilt) == hash(plat)
    lighter = dataclasses.replace(
        plat, components=plat.components[:-1] + (dataclasses.replace(
            plat.components[-1], name=plat.components[-1].name + "_b"),))
    assert lighter != plat
    keys = {plat: "a", lighter: "b"}
    assert keys[rebuilt] == "a" and keys[lighter] == "b"


def test_registry_lookup():
    aria2.platforms()
    assert {"aria2", "aria2_display", "aria2_capture_only"} <= \
        set(platform_registry.names())
    assert platform_registry.get("aria2") is aria2.aria2_platform()
    with pytest.raises(KeyError):
        platform_registry.get("nonexistent_platform")


def test_variant_validates_names(plat):
    with pytest.raises(KeyError):
        plat.variant("bad", drop=("not_a_component",))


# ---------------------------------------------------------------------------
# new knobs + SKU variants through the same API
# ---------------------------------------------------------------------------

def test_upload_duty_gating_reduces_power(plat):
    base = ScenarioSet.build([{"on_device": ()}])
    gated = base.with_knob(upload_duty=0.35)
    p0, p1 = (float(scenarios.total_mw(plat, s)[0]) for s in (base, gated))
    assert p1 < p0
    # saving is bounded by the radio's throughput term
    wifi_col = plat.component_names().index("wifi_combo")
    loads = scenarios.component_loads(plat, base)
    assert p0 - p1 < float(loads[0, wifi_col]) / dict(plat.rails)["rf"]


def test_mcs_tier_scales_radio(plat):
    rows = [{"on_device": (), "mcs_tier": m}
            for m in range(len(scenarios.MCS_TIERS))]
    totals = np.asarray(scenarios.total_mw(plat, ScenarioSet.build(rows)))
    # energy/bit and link scales are monotone across the defined tiers
    assert totals[0] < totals[1] < totals[2]


def test_display_variant_brightness():
    disp = aria2.aria2_display_platform()
    rows = [{"on_device": (), "brightness": b} for b in (0.0, 0.5, 1.0)]
    totals = np.asarray(scenarios.total_mw(disp, ScenarioSet.build(rows)))
    assert totals[0] < totals[1] < totals[2]
    # baseline aria2 has no display load: brightness is inert there
    base = np.asarray(scenarios.total_mw(
        aria2.aria2_platform(), ScenarioSet.build(rows)))
    np.testing.assert_allclose(base[0], base[2], rtol=1e-7)


def test_capture_only_sku_is_cheaper(plat):
    cap = aria2.aria2_capture_only_platform()
    assert len(cap) < len(plat)
    sset = ScenarioSet.build([{"on_device": ()}])
    assert float(scenarios.total_mw(cap, sset)[0]) < \
        float(scenarios.total_mw(plat, sset)[0])


def test_unsupported_placement_rejected(plat):
    """A SKU without ML IPs cannot claim on-device vio/ht savings."""
    cap = aria2.aria2_capture_only_platform()
    assert set(cap.supported_primitives()) == {"asr"}
    with pytest.raises(ValueError, match="cannot run"):
        scenarios.total_mw(cap, ScenarioSet.build(
            [{"on_device": ("hand_tracking",)}]))
    # ASR kept its DSP, so it still evaluates
    t = scenarios.total_mw(cap, ScenarioSet.build([{"on_device": ("asr",)}]))
    assert np.isfinite(float(t[0]))
    # mismatched primitive ordering is rejected, not silently misread
    weird = ScenarioSet.build([{"on_device": ()}],
                              primitives=tuple(reversed(PRIMITIVES)))
    with pytest.raises(ValueError, match="do not match"):
        scenarios.total_mw(plat, weird)


def test_bad_knob_values_rejected():
    with pytest.raises(ValueError, match="mcs_tier"):
        ScenarioSet.build([{"mcs_tier": 99}])
    with pytest.raises(ValueError, match="unknown primitive"):
        ScenarioSet.build([{"on_device": ("telepathy",)}])


def test_unit_fraction_knobs_rejected_outside_01():
    """upload_duty / brightness are [0, 1] fractions: a negative duty
    silently produced negative WiFi power before the guard."""
    for knob in ("upload_duty", "brightness"):
        for bad in (-0.1, 1.5):
            with pytest.raises(ValueError, match=knob):
                ScenarioSet.build([{knob: bad}])
            with pytest.raises(ValueError, match=knob):
                ScenarioSet.build([{}]).with_knob(**{knob: bad})
        # boundary values are legal, scalar or per-row array
        ScenarioSet.build([{knob: 0.0}, {knob: 1.0}])
        ScenarioSet.build([{}, {}]).with_knob(**{knob: np.array([0.0, 1.0])})
    with pytest.raises(ValueError, match="upload_duty"):
        ScenarioSet.grid(placements=((),), compressions=(1.0,),
                         fps_scales=(1.0,), upload_duties=(-0.5,))


def test_capture_only_rejects_every_unsupported_placement():
    """Every placement the capture-only SKU cannot run on-device raises
    (only ASR kept its accelerator)."""
    cap = aria2.aria2_capture_only_platform()
    unsupported = [p for p in cap.primitives
                   if p not in cap.supported_primitives()]
    assert unsupported
    for p in unsupported:
        with pytest.raises(ValueError, match="cannot run"):
            scenarios.evaluate(cap, ScenarioSet.build(
                [{"on_device": (p,)}]))


def test_reduced_sku_empty_grid_roundtrips_json():
    """Empty-placement grids on reduced SKUs evaluate identically through
    a JSON round-trip of the platform (duty tables included)."""
    for plat in (aria2.aria2_capture_only_platform(),
                 aria2.aria2_display_platform()):
        rebuilt = PlatformSpec.from_dict(
            json.loads(json.dumps(plat.to_dict())))
        assert rebuilt == plat
        assert rebuilt.duty_tables == plat.duty_tables
        sset = ScenarioSet.grid(placements=((),),
                                compressions=(4.0, 32.0),
                                fps_scales=(1.0, 8.0))
        np.testing.assert_array_equal(
            np.asarray(scenarios.total_mw(rebuilt, sset)),
            np.asarray(scenarios.total_mw(plat, sset)))


def test_legacy_isp_duty_serialization_still_loads(plat):
    """Pre-duty_tables JSON (bare "isp_duty" list) still deserializes."""
    d = plat.to_dict()
    d["isp_duty"] = d.pop("duty_tables")["isp"]
    rebuilt = PlatformSpec.from_dict(json.loads(json.dumps(d)))
    assert rebuilt.isp_duty == plat.isp_duty
    # tables the old schema lacked fall back to constant defaults
    assert rebuilt.duty_table("npu", 0.0) == (0.0,) * 16


def test_sweep_row_labels_lockstep_with_grid(plat):
    """compression_sweep and pareto row labels must match the
    ScenarioSet.grid ordering they were evaluated under — a grid-order
    change cannot silently mislabel rows."""
    from repro.core import dse

    comps = (1, 2, 4, 8, 16, 32, 64, 128)
    fpss = (1, 2, 4, 8, 16, 32)
    rows = dse.compression_sweep(compressions=comps, fps_scales=fpss)
    ref = ScenarioSet.grid(placements=((),),
                           compressions=[float(c) for c in comps],
                           fps_scales=[float(f) for f in fpss])
    assert len(rows) == len(ref)
    for i, r in enumerate(rows):
        assert float(r["compression"]) == float(ref.compression[i]), i
        assert float(r["fps_scale"]) == float(ref.fps_scale[i]), i

    pcomps = (4, 10, 20, 40)
    pts, _ = dse.pareto(compressions=pcomps)
    pref = ScenarioSet.grid(placements=all_placements(),
                            compressions=[float(c) for c in pcomps],
                            fps_scales=(1.0,))
    assert len(pts) == len(pref)
    for i, p in enumerate(pts):
        assert p["on_device"] == ("+".join(pref.on_device(i)) or "(none)"), i
        assert float(p["compression"]) == float(pref.compression[i]), i


def test_category_breakdown_sums_to_total(plat):
    sset = ScenarioSet.grid(placements=((), tuple(PRIMITIVES)),
                            compressions=(10.0,), fps_scales=(1.0,))
    rep = scenarios.evaluate(plat, sset)
    cats = rep.category_breakdown()
    total = sum(np.asarray(v) for v in cats.values())
    np.testing.assert_allclose(total, np.asarray(rep.total_mw), rtol=1e-5)


# ---------------------------------------------------------------------------
# Ray-Ban-class + puck-split SKUs, platform diffs / ablation helper
# ---------------------------------------------------------------------------

def test_rayban_cam_sku(plat):
    """Camera-only SKU: pure registry data, ASR is the only on-device
    primitive, and the dropped GS/ET streams vanish from the uplink."""
    rb = aria2.rayban_cam_platform()
    assert platform_registry.get("rayban_cam") is rb
    assert set(rb.supported_primitives()) == {"asr"}
    assert len(rb) < len(aria2.aria2_capture_only_platform()) < len(plat)
    raw = dict(rb.raw_mbps)
    assert raw["gs"] == raw["et"] == raw["gs_vio_share"] == 0.0
    sset = ScenarioSet.build([{"on_device": ()}])
    assert float(scenarios.total_mw(rb, sset)[0]) < \
        float(scenarios.total_mw(aria2.aria2_capture_only_platform(),
                                 sset)[0])
    # uplink carries only the RGB + audio + telemetry streams
    mbps = float(scenarios.offloaded_mbps(rb, sset)[0])
    full = float(scenarios.offloaded_mbps(plat, sset)[0])
    assert mbps < full / 3
    with pytest.raises(ValueError, match="cannot run"):
        scenarios.total_mw(rb, ScenarioSet.build([{"on_device": ("vio",)}]))
    # JSON round-trip preserves the raw_mbps override
    rebuilt = PlatformSpec.from_dict(json.loads(json.dumps(rb.to_dict())))
    assert rebuilt == rb


def test_puck_split_sku(plat):
    """Glasses half of the puck split: no ML IPs, short-range-link
    radio coefficients, cheaper at full offload than the baseline."""
    puck = aria2.aria2_puck_split_platform()
    assert platform_registry.get("aria2_puck_split") is puck
    th = puck.theta_dict()
    assert th["wifi_mw_per_mbps"] < plat.theta_dict()["wifi_mw_per_mbps"]
    sset = ScenarioSet.build([{"on_device": ()}])
    assert float(scenarios.total_mw(puck, sset)[0]) < \
        float(scenarios.total_mw(plat, sset)[0])
    assert "vio" not in puck.supported_primitives()


def test_variant_raw_mbps_override_validated(plat):
    with pytest.raises(KeyError, match="unknown raw streams"):
        plat.variant("bad", raw_mbps={"not_a_stream": 1.0})
    with pytest.raises(KeyError, match="unknown ip rates"):
        plat.variant("bad", ip_rates={"npu_htt": 0.0})   # typo'd key
    v = plat.variant("ok", raw_mbps={"et": 0.0})
    assert dict(v.raw_mbps)["et"] == 0.0
    assert dict(v.raw_mbps)["rgb"] == dict(plat.raw_mbps)["rgb"]


def test_rayban_sheds_dropped_sensor_traffic(plat):
    """The SKU's uplink carries no traffic from sensors it dropped:
    one IMU (not two), no GNSS/mag/baro in the aux stream."""
    raw = dict(aria2.rayban_cam_platform().raw_mbps)
    base = dict(plat.raw_mbps)
    assert raw["imu"] == pytest.approx(base["imu"] / 2)
    assert raw["aux"] < base["aux"]


def test_platform_diff(plat):
    from repro.core.platform import diff

    rb = aria2.rayban_cam_platform()
    d = diff(plat, rb)
    assert d["a"] == "aria2" and d["b"] == "rayban_cam"
    assert "npu_ml" in d["dropped"] and "gs_camera_0" in d["dropped"]
    assert d["added"] == []
    assert "coproc_soc_base" in d["changed"]
    assert d["raw_mbps"]["gs"][1] == 0.0
    assert d["theta"] == {}
    # identity diff is empty
    d0 = diff(plat, plat)
    assert not (d0["added"] or d0["dropped"] or d0["changed"]
                or d0["theta"] or d0["raw_mbps"])
    # theta-only variants show up in the theta section
    puck = aria2.aria2_puck_split_platform()
    assert "wifi_link_mw" in diff(plat, puck)["theta"]


def test_platform_ablation_rows(plat):
    from repro.core import dse

    rows = dse.platform_ablation(
        names=("aria2", "rayban_cam", "aria2_capture_only"),
        on_device=("asr", "vio"))
    assert [r["platform"] for r in rows] == \
        ["aria2", "rayban_cam", "aria2_capture_only"]
    assert rows[0]["delta_mw_vs_baseline"] == 0.0
    assert rows[0]["on_device"] == "asr+vio"
    # unsupported placements downshift instead of raising
    assert rows[1]["on_device"] == "asr"
    assert all(r["delta_mw_vs_baseline"] < 0 for r in rows[1:])
    assert "npu_ml" in rows[1]["vs_baseline"]["dropped"]


# ---------------------------------------------------------------------------
# offload fleet sizing fallback (no dry-run artifacts)
# ---------------------------------------------------------------------------

def test_size_fleet_missing_artifact_fallback(tmp_path):
    rows = offload.size_fleet(aria2.FULL_OFFLOAD, n_users=1000, duty=1.0,
                              results_dir=tmp_path)
    for r in rows:
        assert np.isfinite(r["pods"])
        if r.get("note") != "computed on-device":
            assert r["note"] == "missing_artifact"
            assert r["pods"] > 0


def test_fleet_grid_one_batched_eval(tmp_path):
    sset = ScenarioSet.grid(placements=((), tuple(PRIMITIVES)),
                            compressions=(10.0,), fps_scales=(1.0,))
    rows = offload.fleet_grid(sset, n_users=1e6, results_dir=tmp_path)
    assert len(rows) == len(sset)
    # on-device ASR drops the whisper stream from the backend fleet
    assert rows[1]["backend_pods"] < rows[0]["backend_pods"]
    assert all("missing_artifact" in r["note"] for r in rows)


def test_fleet_grid_upload_duty_throttles_backend(tmp_path):
    base = ScenarioSet.build([{"on_device": ()},
                              {"on_device": (), "upload_duty": 0.5}])
    rows = offload.fleet_grid(base, n_users=1e6, results_dir=tmp_path)
    assert rows[1]["uplink_mbps"] == pytest.approx(
        rows[0]["uplink_mbps"] * 0.5, rel=1e-3)
    assert rows[1]["backend_pods"] == pytest.approx(
        rows[0]["backend_pods"] * 0.5, rel=1e-3)
