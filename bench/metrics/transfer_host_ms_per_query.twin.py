"""Host time per finished what-if in the program's transfer phases:
stacking and pushing the batch's inputs to the device
(`repro.daysim.push`) and copying its summaries back
(`repro.daysim.fetch`); from the program's phase counters over the
window, in ms."""
from benchlib import phasecount

NAMES = ("repro.daysim.push", "repro.daysim.fetch")


def read(ctx):
    return phasecount.ms_per_query(ctx, NAMES)
