"""Host milliseconds from a tick's due time to the end of its launch,
median over the window's ticks: the harness's own lateness in feeding
the tick (host clock) plus, inside the program's ``stream.tick`` span of
that feed, the time from its start to the end of its last
``stream.launch`` span (program spans). The device runs the kernel after
the launch returns, so this is the host's part of the tick latency.

Each tick the harness fed is paired with the ``stream.tick`` span its
feed opened, by time: the host clock and the trace's differ by one
offset, taken as the one that puts the most ticks' start within
``TOL_S`` of a span's start; among those, the one nearest the window's
own: the first tick is due when the driver's window opens, just inside
the harness's ``bench.window`` span. A tick with no such span in the
window is left out, so a trace that misses a tick shifts no other."""
import bisect
import statistics

#: A feed's ``stream.tick`` span opens microseconds after the harness
#: takes the tick's start time; ticks are a second apart.
TOL_S = 0.005


def pair(calls, ticks, anchor):
    """``[(call, (start_ns, end_ns)), ...]``: each call with the tick span
    that starts within ``TOL_S`` of its ``t_start`` under the offset that
    pairs the most calls, the one nearest ``anchor`` (seconds) on a tie."""
    starts = [s / 1e9 for s, _ in ticks]

    def matched(off):
        out = []
        for c in calls:
            k = bisect.bisect_left(starts, c["t_start"] + off - TOL_S)
            if k < len(starts) and starts[k] <= c["t_start"] + off + TOL_S:
                out.append((c, ticks[k]))
        return out

    offsets = {s - c["t_start"] for c in calls for s in starts}
    best = max(offsets, default=None,
               key=lambda o: (len(matched(o)), -abs(o - anchor)))
    return [] if best is None else matched(best)


def read(rec):
    tr = rec["trace"]
    ticks = sorted((s, e) for n, s, e in tr.host
                   if n == "stream.tick" and tr.t0 <= s < tr.t1)
    launches = [(s, e) for n, s, e in tr.host if n == "stream.launch"]
    per_tick = []
    calls = rec["calls"]
    anchor = tr.t0 / 1e9 - min((c["t_due"] for c in calls), default=0.0)
    for call, (s, e) in pair(calls, ticks, anchor):
        ends = [b for a, b in launches if s <= a and b <= e]
        if ends:
            per_tick.append(1e3 * (call["t_start"] - call["t_due"])
                            + (max(ends) - s) / 1e6)
    return statistics.median(per_tick) if per_tick else None
