"""Device time of one model step: the step program's time in the trace
(`transformer.decode_step` under the engine's jit) over the model steps
of the traced `run()` calls, in ms."""
from benchlib import serve_steps


def read(ctx):
    n = len(serve_steps.traced_steps(ctx))
    t = serve_steps.step_program_s(ctx)
    return 1e3 * t / n if n and t > 0 else None
