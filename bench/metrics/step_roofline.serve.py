"""The model step's share of its roofline: the least time the chip
could take for the traced steps' work (`bench/work/lm_step.py`, each
step at its rows and attended positions; the larger of FLOPs over the
bf16 peak and bytes over the memory's bandwidth) over the step
program's device time, in %."""
from benchlib import serve_steps


def read(ctx):
    st = serve_steps.traced_steps(ctx)
    secs = serve_steps.step_program_s(ctx)
    if not st or secs <= 0:
        return None
    p = ctx["peaks"]
    least = 0.0
    for rows, att in st:
        w = serve_steps.step_work(ctx, rows, att)
        least += max(w["flops"] / p["bf16_flops_per_s"],
                     w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
