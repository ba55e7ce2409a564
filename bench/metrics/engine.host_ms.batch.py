"""Host milliseconds the engine spends per batch call, from the
program's own spans: over the harness's ``bench.engine`` spans that
start in the traced window (one ``sdtw(...)`` call each), the median of
the summed durations of the ``engine.prepare`` and ``engine.launch``
spans inside each. The program's twin of
``engine.host_ms_per_call.batch``; None where the program has no such
spans."""
import statistics

ENGINE = ("engine.prepare", "engine.launch")


def read(rec):
    tr = rec["trace"]
    calls = [(s, e) for n, s, e in tr.host
             if n == "bench.engine" and tr.t0 <= s < tr.t1]
    eng = [(s, e) for n, s, e in tr.host if n in ENGINE]
    per_call, found = [], False
    for s, e in calls:
        inside = [b - a for a, b in eng if s <= a and b <= e]
        found |= bool(inside)
        per_call.append(sum(inside) / 1e6)
    return statistics.median(per_call) if found else None
