"""Tests of what the benchmark reads of the program's own phases: the
trace reduction of its spans and device scopes (`benchlib.phasetrace`),
the phase counters (`benchlib.phasecount`) and the per-layer readers
built on them, all on synthetic inputs."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import cells, phasecount, phasetrace, trace  # noqa: E402
from benchlib.trace import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW_READERS = ("assembly_host_ms_per_query.twin",
               "transfer_host_ms_per_query.twin",
               "report_host_ms_per_query.twin", "setup_flops_s.twin",
               "setup_compile_s.twin")
OLD_READERS = ("queue_wait_ms_p95.twin", "batch_items_mean.twin",
               "host_ms_per_query.twin",
               "day_program_device_ms_per_query.twin",
               "day_scan_roofline.twin", "device_idle_share.twin")

# (name, start, end) of the program's spans: two micro-batches
PROGRAM = [("repro.twin.batch#batch=1,items=1#", 5, 50),
           ("repro.twin.group", 6, 15), ("repro.daysim.push", 16, 19),
           ("repro.daysim.dispatch", 19, 20), ("repro.daysim.wait", 20, 46),
           ("repro.daysim.fetch", 46, 48), ("repro.daysim.report", 48, 49),
           ("repro.twin.batch", 52, 88), ("repro.twin.group", 52, 66),
           ("repro.daysim.assemble", 53, 65)]
# (scope, start, end) of the device's ops; a loop's event spans its body
SCOPED = [("day_scan", 20, 40), ("day_scan", 22, 30), ("gather", 40, 45),
          (None, 70, 72), ("front", 72, 85), ("front", 110, 120)]


def _harness():
    return [Event(HOST, "python", "bench:window", 0, 100),
            Event(HOST, "python", "bench:run", 5, 50),
            Event(HOST, "python", "bench:run", 50, 95),
            Event(DEV, "XLA Modules", "jit_fused_batch(1)", 20, 45),
            Event(DEV, "XLA Modules", "jit_fused_batch(2)", 70, 85),
            Event(DEV, "XLA Ops", "while.3", 20, 40),
            Event(DEV, "XLA Ops", "fusion.7", 22, 30),
            Event(DEV, "XLA Ops", "fusion.1", 40, 45),
            Event(DEV, "XLA Ops", "copy.1", 70, 72),
            Event(DEV, "XLA Ops", "fusion.2", 72, 85),
            Event(DEV, "XLA Ops", "fusion.2", 110, 120)]


def _program():
    spans = [(phasetrace.span_name(n), {}, s, e) for n, s, e in PROGRAM]
    ops = [(DEV, scope, s, e) for scope, s, e in SCOPED]
    return spans, ops


def test_span_names_and_scopes():
    assert phasetrace.span_name("repro.daysim.push#batch=3,items=1#") \
        == "repro.daysim.push"
    assert phasetrace.scope_of("jit(fused_batch)/vmap(day_scan)/while") \
        == "day_scan"
    assert phasetrace.scope_of("jit(fused)/row_stage/jit(f)/gather") \
        == "row_stage"
    assert phasetrace.scope_of("jit(_where)/select_n") is None
    assert phasetrace.scope_of("jit(fused)/frontier/add") is None


def test_program_spans_leave_every_existing_key_and_reader_unchanged():
    """The program's host spans in the trace change nothing that
    `trace.reduce` gives, nor any existing reader's value."""
    with_program = _harness() + [Event(HOST, "python", n, s, e)
                                 for n, s, e in PROGRAM]
    a, b = trace.reduce(_harness()), trace.reduce(with_program)
    assert a == b
    cell = cells.load(ROOT, "twin_steady")
    done = [{"req": {"due": 0.1 * i}, "wait_s": 0.01 * i,
             "report": SimpleNamespace(combos=[{}] * 63)} for i in range(4)]
    base = {"cell": cell, "spans": [("run", 0.0, 1.0), ("run", 1.0, 2.0)],
            "traced_from_s": None, "traced_until_s": None,
            "window": {"done": done, "batches": [(0.0, 1.0, 2),
                                                 (1.0, 2.0, 2)]},
            "peaks": cells.peaks("TPU v5 lite"),
            "inputs": {"steps": 4320, "levels": 4}}
    for name in OLD_READERS:
        read = cells.module("metrics", name).read
        assert read(dict(base, trace=a)) == read(dict(base, trace=b)), name


def test_reduce_fills_program_spans_scopes_and_idle_by_phase():
    red = trace.reduce(_harness())
    add = phasetrace.reduce(red, *_program())
    assert set(add) == {"program_spans", "device_by_scope", "idle_by_phase"}
    assert add["program_spans"] == pytest.approx({
        "repro.twin.batch": 81e-9, "repro.twin.group": 23e-9,
        "repro.daysim.push": 3e-9, "repro.daysim.dispatch": 1e-9,
        "repro.daysim.wait": 26e-9, "repro.daysim.fetch": 2e-9,
        "repro.daysim.report": 1e-9, "repro.daysim.assemble": 12e-9})
    # the loop's body op lies inside the loop; the op after the window
    # counts for nothing
    assert add["device_by_scope"] == {DEV: pytest.approx(
        {"day_scan": 20e-9, "gather": 5e-9, "unscoped": 2e-9,
         "front": 13e-9})}
    # idle [0,20) falls in the group pass, [45,70) in an assembly nested
    # in a group pass, [85,100) inside a `run()` after its batch span
    assert add["idle_by_phase"] == pytest.approx(
        {"repro.twin.group": 20e-9, "repro.daysim.assemble": 25e-9,
         "run": 15e-9})
    assert red["idle_by_span"] == pytest.approx({"run": 60e-9})


def test_reduce_without_program_spans_matches_idle_by_span():
    """A trace of a program that has no spans (the parent of this
    change) gives every idle gap to the harness's spans, as before."""
    red = trace.reduce(_harness())
    add = phasetrace.reduce(red, [], [])
    assert add["idle_by_phase"] == pytest.approx(red["idle_by_span"])
    assert add["program_spans"] == {} and add["device_by_scope"] == {}


def _st(calls, total_ns, self_ns=None, hist=None):
    return {"calls": calls, "total_ns": total_ns,
            "self_ns": total_ns if self_ns is None else self_ns,
            "hist": hist or {}}


C0 = {"repro.nets.measured_flops": _st(1, 4_800_000_000, hist={23: 1}),
      "jax.backend_compile": _st(40, 9_000_000_000, hist={20: 40}),
      "jax.cache_retrieval": _st(30, 2_000_000_000, hist={16: 30}),
      "repro.twin.group": _st(5, 100_000_000, 60_000_000, {15: 5}),
      "repro.daysim.enumerate": _st(10, 4_810_000_000, 10_000_000,
                                    {1: 9, 23: 1})}
C1 = {"repro.nets.measured_flops": C0["repro.nets.measured_flops"],
      "jax.backend_compile": _st(41, 9_500_000_000, hist={20: 40, 19: 1}),
      "jax.cache_retrieval": C0["jax.cache_retrieval"],
      "repro.twin.group": _st(9, 300_000_000, 140_000_000, {15: 8, 18: 1}),
      "repro.daysim.enumerate": _st(18, 4_818_000_000, 18_000_000,
                                    {1: 17, 23: 1}),
      "repro.daysim.assemble": _st(4, 120_000_000, hist={15: 4}),
      "repro.daysim.push": _st(4, 8_000_000, hist={11: 4}),
      "repro.daysim.fetch": _st(4, 4_000_000, hist={10: 4}),
      "repro.daysim.report": _st(4, 400_000, hist={7: 4})}


def _ctx(c0=C0, c1=C1, n=4):
    counters = ({"exec": {}, "phases": c0}, {"exec": {}, "phases": c1})
    if c0 is None:
        counters = ({"exec": {}}, {"exec": {}})
    return {"window": {"counters": counters, "done": [{}] * n}}


def test_counters_difference_and_slowest_bucket():
    d = phasecount.diff(C0, C1)
    assert d["repro.twin.group"] == {"calls": 4, "total_ns": 200_000_000,
                                     "self_ns": 80_000_000,
                                     "hist": {15: 3, 18: 1}}
    assert d["repro.nets.measured_flops"]["hist"] == {}
    assert phasecount.slowest(C0, C1) == {
        "jax.backend_compile": 19, "repro.twin.group": 18,
        "repro.daysim.enumerate": 1, "repro.daysim.assemble": 15,
        "repro.daysim.push": 11, "repro.daysim.fetch": 10,
        "repro.daysim.report": 7}


def test_new_readers_by_hand():
    got = {n: cells.module("metrics", n).read(_ctx()) for n in NEW_READERS}
    assert got == pytest.approx({
        # self time: group 80 ms + enumerate 8 ms + assemble 120 ms
        "assembly_host_ms_per_query.twin": 208.0 / 4,
        "transfer_host_ms_per_query.twin": 12.0 / 4,
        "report_host_ms_per_query.twin": 0.4 / 4,
        "setup_flops_s.twin": 4.8,
        "setup_compile_s.twin": 9.0})


@pytest.mark.parametrize("ctx", [_ctx(c0=None), _ctx(n=0),
                                 {"window": {"done": [{}]}}])
def test_new_readers_give_nothing_without_counters(ctx):
    """A program without the ``phases`` tier, or a window that finished
    nothing, gives no value and raises nothing."""
    for name in NEW_READERS[:3]:
        assert cells.module("metrics", name).read(ctx) is None
    if ctx["window"].get("counters") is None or "phases" not in \
            ctx["window"]["counters"][0]:
        for name in NEW_READERS[3:]:
            assert cells.module("metrics", name).read(ctx) is None


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) pairs."""
    def varint(n):
        out = b""
        while True:
            b, n = n & 0x7F, n >> 7
            out += bytes([b | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _plane(name: str) -> bytes:
    stat_meta = [(7, "tf_op"), (8, "hlo_category"),
                 (9, "jit(fused_batch)/vmap(gather)/take")]
    meta = [(3, _pb((1, 3), (2, "%while.4 = (s32[]) while()"),
                    (4, "while.4"),
                    (5, _pb((1, 8), (5, "loop"))),
                    (5, _pb((1, 7),
                            (5, "jit(fused_batch)/vmap(day_scan)/while"))))),
            (4, _pb((1, 4), (2, "%fusion.24 = f32[] fusion()"),
                    (5, _pb((1, 7), (7, 9))))),          # an interned value
            (5, _pb((1, 5), (2, "copy.1")))]             # no op_name
    return _pb((1, 1), (2, name),
               (3, _pb((2, "XLA Ops"))),
               *[(4, _pb((1, k), (2, m))) for k, m in meta],
               *[(5, _pb((1, k), (2, _pb((1, k), (2, n)))))
                 for k, n in stat_meta])


def test_op_paths_read_from_event_metadata():
    raw = _pb((1, _plane(DEV)), (1, _plane(HOST)))
    assert phasetrace.op_paths(raw) == {DEV: {
        "%while.4 = (s32[]) while()": "jit(fused_batch)/vmap(day_scan)/while",
        "while.4": "jit(fused_batch)/vmap(day_scan)/while",
        "%fusion.24 = f32[] fusion()": "jit(fused_batch)/vmap(gather)/take"}}
