"""What the serving cells' readers share: the model steps of the
`run()` calls inside the trace, their work, and the device time of the
step program.

A batch record of the serve adapter is (start, end, finished, items,
prompt steps, decode steps).  The engine feeds a prompt one token a
step, so prompt step t attends t + 1 positions, and decode step k after
a prompt of P tokens attends P + k + 1.
"""
from __future__ import annotations

from . import cells, layers


def traced_batches(ctx: dict) -> list:
    return [b for inside, b in zip(layers._traced(ctx, "run"),
                                   ctx["window"].get("batches", []))
            if inside]


def steps(batch: tuple) -> list:
    """(rows, attended positions) of each model step of one batch."""
    _, _, _, rows, prompt, decode = batch
    return ([(rows, t + 1) for t in range(prompt)]
            + [(rows, prompt + k + 1) for k in range(decode)])


def traced_steps(ctx: dict) -> list:
    return [s for b in traced_batches(ctx) for s in steps(b)]


def step_work(ctx: dict, rows: int, attended: int) -> dict:
    inp = ctx["inputs"]
    m = inp["m"]
    return cells.module("work", "lm_step", ctx["cell"].bench_dir).work(
        batch=rows, attended=attended, n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        d_ff=m["d_ff"], vocab=m["vocab"], param_bytes=inp["param_bytes"],
        cache_bytes=inp["cache_bytes"], logit_bytes=inp["logit_bytes"])


def step_program_s(ctx: dict) -> float:
    """Device seconds of the step program: the compiled program that
    took the most device time in the trace (the engine's jitted
    `decode_step`, one program a batch size, all of one name)."""
    red = ctx["trace"]
    n = max(1, len(red["devices"]))
    return max(red["by_module"].values(), default=0.0) / n
