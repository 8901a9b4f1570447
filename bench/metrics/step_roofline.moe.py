"""The model step's share of its roofline: the least time the chip could
take for the traced steps' work (`bench/work/latent_moe_step.py`, each
step at its rows, attended positions and distinct routed experts read; the
larger of FLOPs over the bf16 peak and bytes over the memory's bandwidth)
over the step program's device time, in %."""
from benchlib import moe_steps, serve_steps


def read(ctx):
    st = moe_steps.traced_steps(ctx)
    secs = serve_steps.step_program_s(ctx)
    if not st or secs <= 0:
        return None
    p = ctx["peaks"]
    least = 0.0
    for rows, att, experts in st:
        w = moe_steps.step_work(ctx, rows, att, experts)
        least += max(w["flops"] / p["bf16_flops_per_s"],
                     w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
