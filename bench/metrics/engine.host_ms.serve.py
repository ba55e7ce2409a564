"""Host milliseconds the engine spends per served dispatch: over the
``serve.execute`` spans that start in the traced window (one coalesced
group on a pool worker), the mean of the summed durations of the
``engine.prepare`` and ``engine.launch`` spans inside each (program
spans, ``repro.serve.telemetry``). The device runs the kernel after
``engine.launch`` returns, so this is host work only. Spans are matched
by time alone: exact with the one pool worker the served cells run."""
import bisect

ENGINE = ("engine.prepare", "engine.launch")


def read(rec):
    tr = rec["trace"]
    execs = [(s, e) for n, s, e in tr.host
             if n == "serve.execute" and tr.t0 <= s < tr.t1]
    if not execs:
        return None
    eng = sorted((s, e) for n, s, e in tr.host if n in ENGINE)
    starts = [s for s, _ in eng]
    total = 0.0
    for s, e in execs:
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        total += sum(b - a for a, b in eng[lo:hi] if b <= e)
    return total / len(execs) / 1e6
