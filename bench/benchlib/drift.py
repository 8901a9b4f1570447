"""Records of a configuration file that the program does not hold as
stated.

The plain references read their tables (duty tables, IP rates, fitted
coefficients, stream rates) from the configuration file.  The program
derives the same tables itself, in every process, from its nets, its
calibration and its workload model.  Set-up compares the two, record by
record, so that a fault in that derivation shows as `config_drift`,
which has the limit 0.
"""
from __future__ import annotations

import numpy as np


def registry_drift(cfg: dict) -> int:
    """How many of the file's records differ from the program's
    registries and constants.  Builds the platform registry, as
    `DesignTwin()` and the fleet do (`aria2.platforms()`, which derives
    the measured FLOPs of the nets)."""
    from repro.core import aria2, daysim, offload, scenarios
    from repro.core import platform as registry
    aria2.platforms()
    bad = 0
    for n, rec in cfg["platforms"].items():
        bad += registry.get(n).to_dict() != rec
    for n, rec in cfg["schedules"].items():
        bad += daysim.get_schedule(n).to_dict() != rec
    for n, rec in cfg["policies"].items():
        bad += daysim.get_policy(n).to_dict() != rec
    for n, rec in cfg["batteries"].items():
        bad += daysim.battery_for(n).to_dict() != rec
    bad += daysim.DEFAULT_THERMAL.to_dict() != cfg["thermal"]
    bad += [list(t) for t in scenarios.MCS_TIERS] != cfg["mcs_tiers"]
    bad += not np.array_equal(offload.stream_rates()["tok_per_cap"],
                              np.asarray(cfg["stream_tok_per_cap"]))
    bad += (daysim.DEFAULT_STANDBY_MW, daysim.DEFAULT_SHUTDOWN_C) != (
        cfg["standby_mw"], cfg["shutdown_c"])
    if "pricing" in cfg:
        p = cfg["pricing"]
        bad += (offload.POD_CAPEX_USD_PER_HOUR, offload.POD_POWER_KW,
                offload.USD_PER_KWH) != (p["pod_capex_usd_per_hour"],
                                          p["pod_power_kw"],
                                          p["usd_per_kwh"])
    return int(bad)
