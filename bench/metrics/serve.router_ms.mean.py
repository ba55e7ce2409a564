"""Mean milliseconds from a request's admission to the delivery of its
answer, as the Router's own request traces time it (``Router.stats()``:
the change of its exact running mean over the window's requests). The
served latency less this is the client's own wait: the due-to-submit lag
and the fetch of the answer to the host."""


def read(rec):
    c = rec["counters"]
    if not c.get("completed"):
        return None
    return c["router_ms_sum"] / c["completed"]
