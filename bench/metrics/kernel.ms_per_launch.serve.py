"""Device milliseconds of the sDTW kernel per engine launch in the
served cells: the device time of the trace's ``/sdtw_pallas$``
operations in the window (the operations ``sdtw_roofline`` reads) over
the ``engine.launch`` spans that start in it. One launch per dispatch:
the fixed kernel cost each dispatch pays, however few its queries."""
import re

KERNEL = re.compile(r"/sdtw_pallas$")


def read(rec):
    tr = rec["trace"]
    launches = sum(1 for n, s, _ in tr.host
                   if n == "engine.launch" and tr.t0 <= s < tr.t1)
    seconds = tr.op_seconds(KERNEL)
    if not launches or seconds <= 0:
        return None
    return 1e3 * seconds / launches
