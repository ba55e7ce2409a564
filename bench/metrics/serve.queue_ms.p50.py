"""Median milliseconds a request waits in the Router between admission
and dispatch (its admission queue and coalescing window), from the
Router's own request traces (``Router.stats().p50_queue_us``, a ring
that holds only the window's requests)."""


def read(rec):
    c = rec["counters"]
    if not c.get("completed"):
        return None
    return c["queue_p50_ms"]
