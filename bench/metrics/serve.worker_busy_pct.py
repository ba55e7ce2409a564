"""Share of the traced window in which a pool worker was executing a
group, in %: the union of the ``serve.execute`` spans that start in the
window (clipped at its end) over the window (program spans). With the
one pool worker of the served cells, near 100 % means the worker's host
path sets the rate the Router can serve."""


def read(rec):
    tr = rec["trace"]
    execs = sorted((s, min(e, tr.t1)) for n, s, e in tr.host
                   if n == "serve.execute" and tr.t0 <= s < tr.t1)
    if not execs:
        return None
    busy, end = 0.0, tr.t0
    for s, e in execs:
        s = max(s, end)
        if e > s:
            busy += e - s
            end = e
    return 100.0 * busy / (tr.t1 - tr.t0)
