"""Model FLOP utilization of the served path: the model FLOPs of every
token the traced `run()` calls processed (prompt and generated tokens,
`bench/work/lm_step.py`) over the traced window's length times the
chip's bf16 peak, in %."""
from benchlib import serve_steps


def read(ctx):
    st = serve_steps.traced_steps(ctx)
    win = ctx["trace"]["window_s"]
    if not st or win <= 0:
        return None
    flops = sum(serve_steps.step_work(ctx, r, a)["flops"] for r, a in st)
    return 100.0 * flops / (win * ctx["peaks"]["bf16_flops_per_s"])
