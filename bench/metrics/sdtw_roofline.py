"""Share of the roofline that the sDTW Pallas kernel reached, in %.

The work is ``bench/work.py``'s count for every call of the traced
window (nominal cells, ops from the recurrence, least HBM bytes); the
time is the device time of the kernel's operations in the trace; the
ceilings are ``bench/peaks.json``'s for the device kind. The sDTW
recurrence moves a few bytes per million operations, so the VPU bound
applies. Read in the batch cells.
"""
import re

from bench import work

#: The kernel's operation in the trace: the ``pallas_call`` that
#: ``repro.kernels.sdtw.ops.sdtw_pallas`` lowers, inside its jitted module.
KERNEL = re.compile(r"/sdtw_pallas$")


def read(rec):
    seconds = rec["trace"].op_seconds(KERNEL)
    calls = rec["calls"]
    if not calls or seconds <= 0:
        return None
    share, _ = work.roofline(sum(c["ops"] for c in calls),
                             sum(c["bytes"] for c in calls), seconds,
                             rec["device_kind"])
    return share
