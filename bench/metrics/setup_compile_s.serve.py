"""Set-up time in JAX's backend compiles (persistent compile cache reads
among them), summed by the adapter's `jax.monitoring` listener before
the window, in s."""


def read(ctx):
    return ctx["inputs"].get("setup_compile_s")
