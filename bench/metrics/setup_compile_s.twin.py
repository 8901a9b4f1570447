"""Set-up time in JAX's backend compiles (`jax.backend_compile`, which
holds the persistent compile cache's reads), from the program's
counters before the window, in s."""
from benchlib import phasecount

NAMES = ("jax.backend_compile",)


def read(ctx):
    return phasecount.setup_s(ctx, NAMES)
