"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, and its
phases (run here at tiny sizes, kernels in interpret mode) pass the
same checks against their host oracles that they must pass on the
chip at full size."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.core import daysim

ROOT = Path(__file__).resolve().parent.parent
TINY_GRID = {"platforms": ("aria2_display", "aria2_puck_split"),
             "designs": daysim.DEFAULT_DESIGNS[:2],
             "schedules": ("commuter",)}
TINY_DT_S = 120.0


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_failed_check_exits(smoke):
    with pytest.raises(SystemExit):
        smoke.check("always_fails", False)


def test_twin_phase_tiny(smoke, capsys):
    base, ref = smoke.phase_twin_xla(TINY_GRID, TINY_DT_S)
    assert len(base) == len(ref) == 12
    out = capsys.readouterr().out
    assert "FAILED" not in out and "twin_no_retrace_after_warmup: ok" in out


def test_fleet_phase_tiny(smoke, capsys):
    smoke.phase_fleet(300, TINY_DT_S, 8)
    assert "FAILED" not in capsys.readouterr().out


def test_backend_phase_smoke_config(smoke, capsys):
    smoke.phase_backend("granite-3-2b", smoke=True)
    out = capsys.readouterr().out
    assert "FAILED" not in out and "backend_greedy_token: ok" in out
