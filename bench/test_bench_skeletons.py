"""The reader of `skeleton_hit_share.twin`, on synthetic counter
snapshots: the share of the window's host assemblies that hit the
program's ``skeletons`` tier, and nothing where the tier is missing or
no assembly ran."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import cells  # noqa: E402

NAME = "skeleton_hit_share.twin"


def _tier(hits, misses):
    return {"hits": hits, "misses": misses, "evictions": 0, "size": 1}


def _ctx(c0, c1, n=4):
    return {"window": {"counters": ({"exec": {}, **c0}, {"exec": {}, **c1}),
                       "done": [{}] * n}}


def _read(ctx):
    return cells.module("metrics", NAME).read(ctx)


def test_reads_the_share_of_assemblies_that_hit_a_skeleton():
    # set-up built one skeleton and hit it 30 times; the window hit it
    # 279 times and built one more
    ctx = _ctx({"skeletons": _tier(30, 1)}, {"skeletons": _tier(309, 2)})
    assert _read(ctx) == pytest.approx(100.0 * 279 / 280)


@pytest.mark.parametrize("ctx", [
    _ctx({}, {}),                                   # no tier: the parent
    _ctx({}, {"skeletons": _tier(3, 1)}),
    _ctx({"skeletons": _tier(5, 1)},                # no assembly in the
         {"skeletons": _tier(5, 1)}),               # window
    {"window": {"done": [{}]}},                     # no counters at all
])
def test_gives_none_without_the_tier_or_an_assembly(ctx):
    assert _read(ctx) is None


def test_declared_for_the_twin_cell():
    cell = cells.load(BENCH.parent, "twin_steady")
    m = {x["name"]: x for x in cell.per_layer}[NAME]
    assert (m["unit"], m["better"], m["source"]) == (
        "%", "higher", "program_counter")
    assert m["layer"] == "host assembly and dispatch"
    assert m["moves"] == "whatif_p50_ms"
