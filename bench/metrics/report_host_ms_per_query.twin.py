"""Host time per finished what-if spent building its `DayReport`
(`repro.daysim.report`); from the program's phase counters over the
window, in ms."""
from benchlib import phasecount

NAMES = ("repro.daysim.report",)


def read(ctx):
    return phasecount.ms_per_query(ctx, NAMES)
