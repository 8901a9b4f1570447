"""Set-up time spent deriving the perception nets' FLOPs
(`repro.nets.measured_flops`), from the program's phase counters
before the window, in s."""
from benchlib import phasecount

NAMES = ("repro.nets.measured_flops",)


def read(ctx):
    return phasecount.setup_s(ctx, NAMES)
