"""Model FLOP utilization of the served path: the model FLOPs of every
token the traced `run()` calls processed (prompt and generated tokens,
`bench/work/latent_moe_step.py`: 6 routed and the shared experts a token)
over the traced window's length times the chip's bf16 peak, in %."""
from benchlib import moe_steps


def read(ctx):
    st = moe_steps.traced_steps(ctx)
    win = ctx["trace"]["window_s"]
    if not st or win <= 0:
        return None
    flops = sum(moe_steps.step_work(ctx, r, a, e)["flops"] for r, a, e in st)
    return 100.0 * flops / (win * ctx["peaks"]["bf16_flops_per_s"])
