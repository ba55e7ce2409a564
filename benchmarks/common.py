"""Shared helpers for the benchmark harness.

Harness contract: every benchmark module's ``main()`` *returns* a list of
``(name, us_per_call, derived)`` rows — optionally
``(name, us_per_call, derived, decision)`` where ``decision`` is the
engine's ``DispatchDecision.token()`` (``source:impl``, e.g.
``model:wavefront``) for rows that went through ``impl='auto'`` dispatch;
``benchmarks.run`` owns all printing (and the ``--json`` trajectory
dump, where the 4th element lands as a ``decision`` key). Run
standalone, a module prints its own rows via ``print_rows``.
"""
from __future__ import annotations

import time

import numpy as np

HEADER = "name,us_per_call,derived"


def time_call(fn, *args, repeats: int = 3, warmup: int = 1, **kw):
    """Median wall-time per call in µs (after jit warmup)."""
    for _ in range(warmup):
        r = fn(*args, **kw)
        _block(r)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = fn(*args, **kw)
        _block(r)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _block(x):
    import jax
    jax.block_until_ready(x)


def emit(name: str, us_per_call: float, derived: str = "",
         decision: str | None = None):
    """Build one CSV row per the harness contract: name,us_per_call,derived
    — plus the optional dispatch-decision token (``source:impl``)."""
    if decision is None:
        return (name, float(us_per_call), derived)
    return (name, float(us_per_call), derived, decision)


def format_row(row) -> str:
    name, us, derived = row[0], row[1], row[2]
    line = f"{name},{us:.2f},{derived}"
    if len(row) > 3:
        line += f",{row[3]}"
    return line


def print_rows(rows):
    print(HEADER)
    for row in rows:
        print(format_row(row))


def rows_to_json(rows):
    out = []
    for row in rows:
        d = {"name": row[0], "us_per_call": row[1], "derived": row[2]}
        if len(row) > 3:
            d["decision"] = row[3]
        out.append(d)
    return out
