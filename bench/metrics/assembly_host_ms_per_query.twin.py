"""Host time per finished what-if in the program's assembly phases:
the signature pass of `DesignTwin.query_batch` (`repro.twin.group`),
combo enumeration (`repro.daysim.enumerate`) and the host build after
an assembly-cache miss (`repro.daysim.assemble`), as self time, so
phases nested in one another count once; from the program's phase
counters over the window, in ms."""
from benchlib import phasecount

NAMES = ("repro.twin.group", "repro.daysim.enumerate",
         "repro.daysim.assemble")


def read(ctx):
    return phasecount.ms_per_query(ctx, NAMES)
