"""The reader of `transfer_calls_per_query.twin`, on synthetic counter
snapshots: the change of the program's ``transfers`` tier over the
window per finished what-if, and nothing where the tier is missing."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import cells  # noqa: E402

NAME = "transfer_calls_per_query.twin"


def _tier(h2d, d2h):
    return {"h2d_calls": h2d, "h2d_bytes": 3_400_000 * h2d,
            "d2h_calls": d2h, "d2h_bytes": 3_072 * d2h}


def _ctx(c0, c1, n=4):
    return {"window": {"counters": ({"exec": {}, **c0}, {"exec": {}, **c1}),
                       "done": [{}] * n}}


def _read(ctx):
    return cells.module("metrics", NAME).read(ctx)


def test_reads_the_change_of_the_tier_per_finished_whatif():
    # 3 batches carried 4 what-ifs: 3 pushes and 3 fetches in the window,
    # on top of 10 and 12 made before it
    ctx = _ctx({"transfers": _tier(10, 12)}, {"transfers": _tier(13, 15)})
    assert _read(ctx) == pytest.approx(6 / 4)


@pytest.mark.parametrize("ctx", [
    _ctx({}, {}),                                   # no tier: the parent
    _ctx({}, {"transfers": _tier(3, 3)}),
    _ctx({"transfers": _tier(0, 0)}, {"transfers": _tier(3, 3)}, n=0),
    {"window": {"done": [{}]}},                     # no counters at all
])
def test_gives_none_without_the_tier_or_a_finished_whatif(ctx):
    assert _read(ctx) is None


def test_declared_for_the_twin_cell():
    cell = cells.load(BENCH.parent, "twin_steady")
    m = {x["name"]: x for x in cell.per_layer}[NAME]
    assert m["source"] == "program_counter"
    assert m["layer"] == "host assembly and dispatch"
    assert m["moves"] == "whatif_p50_ms"
