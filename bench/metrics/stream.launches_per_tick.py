"""Device steps the monitoring session launched per tick of the window,
from its own counters (``StreamSession.counters()``): 1 when one launch
advances every stream, as many as the streams when each stream is a
session of its own."""


def read(rec):
    c = rec["counters"]
    if not c.get("ticks") or "launches" not in c:
        return None
    return c["launches"] / c["ticks"]
