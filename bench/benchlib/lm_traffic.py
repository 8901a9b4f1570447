"""Open-loop requests for a served language model, from a mix's data
file.

The pattern of the window -- when each request is due -- is fixed by
the mix's own `pattern_seed` through `openloop.due_times`.  Every
request has the mix's prompt length and answer length.  The run's seed
draws the prompt tokens alone.  So every seed offers the same sizes at
the same instants.

Parameters of a mix:
  "arrivals":   as `openloop.due_times` reads them
  "prompt":     prompt tokens of every request
  "new":        tokens every request generates
  "pattern_seed", "checked", "trace_seconds": as for every mix
"""
from __future__ import annotations

import numpy as np

from . import openloop

# ids 0 and 1 are kept out of prompts: 0 is the id the engine pads with
FIRST_ID = 2


def schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """The window's requests, in due order: {"i", "due" (s from the
    window's start), "prompt" (int32 ids), "new" (tokens to generate)}."""
    pat = openloop.rng(int(mix.get("pattern_seed", 0)), 7)
    due = openloop.due_times(mix["arrivals"], seconds, pat)
    r = openloop.rng(seed)
    return [{"i": i, "due": float(t), "new": int(mix["new"]),
             "prompt": r.integers(FIRST_ID, vocab, int(mix["prompt"]),
                                  dtype=np.int32)}
            for i, t in enumerate(due)]
