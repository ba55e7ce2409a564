"""Host milliseconds the engine takes per batch call, median over the
window's calls: the harness's own host-clock span from entry into
``repro.core.sdtw(...)`` to its return of not-yet-awaited arrays (the
engine's dispatch, padding and host-to-device transfer)."""
import statistics


def read(rec):
    spans = [1e3 * (c["t_return"] - c["t_start"]) for c in rec["calls"]]
    return statistics.median(spans) if spans else None
