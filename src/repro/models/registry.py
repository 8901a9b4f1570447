"""Architecture registry: ``--arch <id>`` -> (config, model module).

Every model module exposes: init, forward, loss_fn, decode_step and
(family-dependent) prefill/init_cache.
"""
from __future__ import annotations

import importlib
from typing import Any

from . import mamba_lm, transformer, whisper

ARCHS = {
    "olmo-1b":             ("repro.configs.olmo_1b", transformer),
    "gemma3-4b":           ("repro.configs.gemma3_4b", transformer),
    "granite-3-2b":        ("repro.configs.granite_3_2b", transformer),
    "yi-34b":              ("repro.configs.yi_34b", transformer),
    "zamba2-1.2b":         ("repro.configs.zamba2_1p2b", mamba_lm),
    "mamba2-2.7b":         ("repro.configs.mamba2_2p7b", mamba_lm),
    "whisper-medium":      ("repro.configs.whisper_medium", whisper),
    "phi-3-vision-4.2b":   ("repro.configs.phi3_vision_4p2b", transformer),
    "moonshot-v1-16b-a3b": ("repro.configs.moonshot_v1_16b_a3b", transformer),
    "dbrx-132b":           ("repro.configs.dbrx_132b", transformer),
}

# The assignment sheet's ten: the dry-run grid (`launch/dryrun.py`,
# `launch/sweep.py`, `results/dryrun/`) covers these and no others.
SHEET = tuple(ARCHS)

# Served at its published widths through `serving/engine.py`, and outside
# the dry-run grid (its latent attention and routed experts have no
# sharded path there).
ARCHS["moonlight-16b-a3b"] = ("repro.configs.moonlight_16b_a3b", transformer)


def get(arch: str, smoke: bool = False):
    """Returns (ModelConfig, model module)."""
    mod_path, model = ARCHS[arch]
    cfg_mod = importlib.import_module(mod_path)
    cfg = cfg_mod.smoke() if smoke else cfg_mod.config()
    return cfg, model


def arch_names() -> list[str]:
    return list(ARCHS)
