"""Device milliseconds of the sDTW kernel per monitoring launch: the
device time of the trace's ``/sdtw_pallas$`` operations in the window
over the program's ``stream.launch`` spans that start in it (one per
tile step of the session)."""
import re

KERNEL = re.compile(r"/sdtw_pallas$")


def read(rec):
    tr = rec["trace"]
    launches = sum(1 for n, s, _ in tr.host
                   if n == "stream.launch" and tr.t0 <= s < tr.t1)
    seconds = tr.op_seconds(KERNEL)
    if not launches or seconds <= 0:
        return None
    return 1e3 * seconds / launches
