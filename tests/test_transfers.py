"""The fused day programs' host-device boundary.

An assembly carries its `dyn` and `ix` trees packed into one contiguous
buffer per dtype (`daysim._pack`, by a `_Layout` fixed by the shape
signature and kept with the assembly's skeleton); the programs unpack
them on the device and return their summary fields stacked in one
float32 array.  These tests pin that the packing loses no bit, that
the packed programs answer bit for bit as the bare bodies do, and that
a batch or a serial query crosses the boundary once each way (the
``transfers`` tier of `daysim.cache_stats()`)."""
from __future__ import annotations

import dataclasses
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import daysim

DT = 600.0
GRIDS = {"default": {},
         "rayban_cam": {"platforms": ("rayban_cam",)}}
SMALL = {"platforms": ("aria2_display",),
         "designs": daysim.DEFAULT_DESIGNS[:2], "schedules": ("commuter",),
         "dt_s": DT}


def _assembly(**grid):
    return daysim._assemble_query(
        **{**daysim._batch_defaults(), "dt_s": DT, **grid})


def _policies(trip: float) -> tuple:
    gov = daysim.get_policy("thermal_governor")
    return ("none", dataclasses.replace(gov, name=f"x{trip}",
                                        temp_trip_c=trip))


def _same_leaves(got, want):
    got_l, got_def = jax.tree_util.tree_flatten(got)
    want_l, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_l, want_l):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("grid", list(GRIDS))
def test_pack_then_unpack_gives_back_every_leaf(grid):
    asm = _assembly(**GRIDS[grid])
    assert asm.layouts == (daysim._layout_of(asm.dyn),
                           daysim._layout_of(asm.ix))
    for layout, tree, bufs in zip(asm.layouts, (asm.dyn, asm.ix),
                                  asm.packed):
        assert tuple(b.dtype for b in bufs) == layout.dtypes
        assert tuple(b.size for b in bufs) == layout.sizes
        assert sum(b.nbytes for b in bufs) == sum(
            np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))
        _same_leaves(daysim._unpack(layout, bufs), tree)
        # on the device, and with a leading batch axis
        _same_leaves(jax.jit(lambda b: daysim._unpack(layout, b))(bufs),
                     tree)
        two = tuple(np.stack([b, b]) for b in bufs)
        _same_leaves(daysim._unpack(layout, two),
                     jax.tree_util.tree_map(lambda x: np.stack([x, x]),
                                            tree))


@pytest.mark.parametrize("batched", [False, True])
def test_packed_program_answers_as_the_bare_body(batched):
    asm = _assembly(**SMALL, policies=_policies(39.0))
    build = daysim._build_fused_batch if batched else daysim._build_fused
    body = build(asm.plats, "xla")
    trees, bufs = (asm.dyn, asm.ix), asm.packed
    if batched:
        trees, bufs = [jax.tree_util.tree_map(lambda x: np.stack([x, x]), t)
                       for t in (trees, bufs)]
    want = jax.jit(body)(*trees)
    out = np.asarray(jax.jit(daysim._build_packed(body, asm.layouts))(*bufs))
    assert out.dtype == np.float32
    for i in range(2 if batched else 1):
        got = daysim._split_summary(out[i] if batched else out)
        assert set(got) == set(want)
        for k, v in want.items():
            w = np.asarray(v)[i] if batched else np.asarray(v)
            assert got[k].dtype == w.dtype, k
            assert got[k].tobytes() == w.tobytes(), k


def _transfers() -> dict:
    return daysim.cache_stats()["transfers"]


def _delta(before: dict) -> dict:
    after = _transfers()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("k", [1, 3, 16])
def test_batch_crosses_once_each_way(k):
    queries = [{"policies": _policies(38.0 + 0.1 * i)} for i in range(k)]
    asms = [_assembly(**SMALL, **q) for q in queries]
    k_b = daysim.bucket_size(k)
    n_b = daysim.bucket_size(asms[0].n_real)
    before = _transfers()
    reps = daysim.day_grid_batch(queries, **SMALL)
    assert len(reps) == k
    assert _delta(before) == {
        "h2d_calls": 1, "d2h_calls": 1,
        "h2d_bytes": k_b * sum(b.nbytes for bufs in asms[0].packed
                               for b in bufs),
        "d2h_bytes": k_b * len(daysim._SUMMARY_KEYS) * n_b * 4}


def test_serial_fused_call_crosses_once_each_way():
    grid = {**SMALL, "policies": _policies(42.5)}
    daysim.day_grid(engine="fused", **grid)    # builds the pipeline
    asm = _assembly(**grid)
    before = _transfers()
    rep = daysim.day_grid(engine="fused", with_front=True, **grid)
    assert len(rep) == asm.n_real
    assert _delta(before) == {
        "h2d_calls": 1, "d2h_calls": 1,
        "h2d_bytes": sum(b.nbytes for b in asm.packed[0]),
        "d2h_bytes": len(daysim._SUMMARY_KEYS)
        * daysim.bucket_size(asm.n_real) * 4}


def test_counts_hold_under_concurrent_transfers():
    """Concurrent `run()` calls push and fetch from several threads; no
    count may be lost."""
    threads, calls = 8, 300
    buf = np.zeros(16, np.float32)
    before = _transfers()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(calls):
                daysim._fetch(daysim._push((buf,))[0])
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    n = threads * calls
    assert _delta(before) == {"h2d_calls": n, "h2d_bytes": n * buf.nbytes,
                              "d2h_calls": n, "d2h_bytes": n * buf.nbytes}
