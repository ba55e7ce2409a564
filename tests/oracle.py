"""The one test oracle: ground truth for sDTW distances, spans, top-K
selections, and alignment paths.

Every implementation test imports from here — never from a production
module's own reference code — so there is exactly one definition of
"correct" for:

  * distances:  the naive numpy DP of ``repro.core.sdtw_ref`` (Algorithm 1
    plus the standard free-start row), re-exported unchanged;
  * spans:      ``sdtw_span_matrix`` adds the start-pointer lane with the
    shared lexicographic rule — a cell's start is the *smallest* row-0
    column among its minimum-cost paths (value ties break toward the
    smaller start);
  * end picks:  leftmost ``argmin`` of the last row;
  * paths:      ``sdtw_path`` re-runs the DP pinned to the reported start
    and traces predecessors diagonal-first, then left, then up — the same
    deterministic convention ``repro.core.traceback`` implements with
    bounded memory;
  * top-K:      ``greedy_topk`` / ``greedy_topk_spans`` — best-first
    select-then-suppress on the full last row, by end distance or by span
    overlap;
  * alerts:     ``sdtw_last_row`` (the cost of the best match ending at
    each column) and ``tile_alerts``, a stream monitor's threshold alerts
    read off it tile by tile.

(The former second oracle, the pure-jnp scan of ``repro.kernels.sdtw.ref``,
is gone: its only non-test use was as a benchmark baseline, which now
lives inline in ``benchmarks/sdtw_kernel_bench.py``.)

Everything here is float64 numpy with explicit loops: slow, unambiguous,
and exact for the integer-valued inputs the bitwise tests feed it.
"""
from __future__ import annotations

import numpy as np

from repro.core.distances import INT_BIG
from repro.core.sdtw_ref import dtw_ref, sdtw_matrix, sdtw_ref  # noqa: F401

__all__ = [
    "sdtw_ref", "sdtw_matrix", "dtw_ref",
    "sdtw_span_matrix", "sdtw_span", "sdtw_end",
    "sdtw_path", "greedy_topk", "greedy_topk_spans", "sdtw_last_row",
    "tile_alerts",
]


def _dist(q, r, metric: str):
    d = np.asarray(q, np.float64) - np.asarray(r, np.float64)
    if metric == "abs_diff":
        return np.abs(d)
    if metric == "square_diff":
        return d * d
    raise ValueError(f"unknown metric {metric!r}")


def sdtw_span_matrix(query, reference, metric: str = "abs_diff",
                     excl_lo=None, excl_hi=None):
    """Full (values, starts) DP: S is the float64 scoring matrix, T[i, j]
    the smallest row-0 column among the minimum-cost paths into (i, j).

    ``excl_lo``/``excl_hi`` ban the half-open reference column range
    ``[excl_lo, excl_hi)`` — banned columns cost inf, exactly the
    engine's per-query exclusion mask (which puts BIG in the distance
    row); a last-row value ≥ INT_BIG / inf therefore means "no
    admissible alignment ends here" on both sides of a differential."""
    q = np.asarray(query, np.float64)
    r = np.asarray(reference, np.float64)
    n, m = len(q), len(r)
    S = np.zeros((n, m))
    T = np.zeros((n, m), np.int64)
    S[0] = _dist(q[0], r, metric)
    T[0] = np.arange(m)
    if excl_lo is not None or excl_hi is not None:
        lo = 0 if excl_lo is None else int(excl_lo)
        hi = 0 if excl_hi is None else int(excl_hi)
        banned = np.zeros((m,), bool)
        banned[max(0, lo):max(0, min(hi, m))] = True
        return _span_matrix_banned(q, r, metric, banned)
    for i in range(1, n):
        di = _dist(q[i], r, metric)
        S[i, 0] = S[i - 1, 0] + di[0]
        T[i, 0] = T[i - 1, 0]
        for j in range(1, m):
            preds = ((S[i - 1, j - 1], T[i - 1, j - 1]),
                     (S[i, j - 1], T[i, j - 1]),
                     (S[i - 1, j], T[i - 1, j]))
            v = min(p[0] for p in preds)
            s = min(p[1] for p in preds if p[0] == v)
            S[i, j] = di[j] + v
            T[i, j] = s
    return S, T


def _span_matrix_banned(q, r, metric, banned):
    """The banned-columns variant of ``sdtw_span_matrix``: a banned
    column's distance row is inf, so no admissible path touches it (inf
    propagates); start pointers follow the same smallest-start rule with
    inf cells keeping a harmless sentinel."""
    n, m = len(q), len(r)
    S = np.zeros((n, m))
    T = np.zeros((n, m), np.int64)
    d0 = _dist(q[0], r, metric)
    d0[banned] = np.inf
    S[0] = d0
    T[0] = np.arange(m)
    for i in range(1, n):
        di = _dist(q[i], r, metric)
        di[banned] = np.inf
        S[i, 0] = S[i - 1, 0] + di[0]
        T[i, 0] = T[i - 1, 0]
        for j in range(1, m):
            preds = ((S[i - 1, j - 1], T[i - 1, j - 1]),
                     (S[i, j - 1], T[i, j - 1]),
                     (S[i - 1, j], T[i - 1, j]))
            v = min(p[0] for p in preds)
            s = min(p[1] for p in preds if p[0] == v)
            S[i, j] = di[j] + v
            T[i, j] = s
    return S, T


def sdtw_span(query, reference, metric: str = "abs_diff"):
    """(distance, start, end): leftmost-argmin end of the last row plus
    that cell's start pointer."""
    S, T = sdtw_span_matrix(query, reference, metric)
    end = int(np.argmin(S[-1]))
    return float(S[-1, end]), int(T[-1, end]), end


def sdtw_end(query, reference, metric: str = "abs_diff") -> int:
    """Leftmost end position attaining the sDTW minimum."""
    return int(np.argmin(sdtw_matrix(query, reference, metric)[-1]))


def sdtw_path(query, reference, start: int, end: int,
              metric: str = "abs_diff") -> np.ndarray:
    """The warping path of span [start, end]: pinned-start window DP (row 0
    finite only at ``start``), traced back diagonal-first, then left, then
    up. Returns (L, 2) (query_row, global_ref_column) pairs, first to
    last."""
    q = np.asarray(query, np.float64)
    w = np.asarray(reference, np.float64)[start:end + 1]
    n, width = len(q), len(w)
    D = _dist(q[:, None], w[None, :], metric)
    S = np.full((n, width), np.inf)
    S[0, 0] = D[0, 0]
    for i in range(1, n):
        S[i, 0] = S[i - 1, 0] + D[i, 0]
        for j in range(1, width):
            S[i, j] = D[i, j] + min(S[i - 1, j - 1], S[i, j - 1],
                                    S[i - 1, j])
    path = []
    i, j = n - 1, width - 1
    while True:
        path.append((i, j))
        if i == 0:
            assert j == 0, "pinned-start path must terminate at column 0"
            break
        here = S[i, j]
        if j > 0 and S[i - 1, j - 1] + D[i, j] == here:
            i, j = i - 1, j - 1
        elif j > 0 and S[i, j - 1] + D[i, j] == here:
            j = j - 1
        else:
            assert S[i - 1, j] + D[i, j] == here
            i = i - 1
    path.reverse()
    out = np.asarray(path, np.int64)
    out[:, 1] += start
    return out


def greedy_topk(last_row, k: int, zone: int):
    """Best-first selection with end-distance suppression on the full DP
    last row (float64) — the semantics ``repro.core.topk`` implements
    streamed. Returns [(distance, end)] with (inf, -1) padding."""
    row = np.asarray(last_row, np.float64).copy()
    out = []
    for _ in range(k):
        j = int(np.argmin(row))
        v = row[j]
        if v >= INT_BIG or not np.isfinite(v):
            out.append((np.inf, -1))
            continue
        out.append((v, j))
        row[np.abs(np.arange(len(row)) - j) <= zone] = np.inf
    return out


def greedy_topk_spans(query, reference, k: int, zone: int,
                      metric: str = "abs_diff", excl_span: bool = False):
    """Span-aware greedy top-K on the full last row: returns
    [(distance, start, end)], suppressing by end distance or (with
    ``excl_span``) by overlap of the zone-widened spans."""
    S, T = sdtw_span_matrix(query, reference, metric)
    row = S[-1].copy()
    starts = T[-1]
    m = len(row)
    out = []
    for _ in range(k):
        j = int(np.argmin(row))
        v = row[j]
        if v >= INT_BIG or not np.isfinite(v):
            out.append((np.inf, -1, -1))
            continue
        s = int(starts[j])
        out.append((v, s, j))
        if excl_span:
            hit = (starts <= j + zone) & (np.arange(m) >= s - zone)
        else:
            hit = np.abs(np.arange(m) - j) <= zone
        row[hit] = np.inf
    return out


def sdtw_last_row(query, reference, metric: str = "abs_diff") -> np.ndarray:
    """The DP's last row, swept one row at a time: the cost of the best
    match of ``query`` ending at each column of ``reference`` (float64)."""
    q = np.asarray(query, np.float64)
    r = np.asarray(reference, np.float64)
    prev = _dist(q[0], r, metric)
    for i in range(1, len(q)):
        di = _dist(q[i], r, metric)
        cur = np.empty_like(prev)
        cur[0] = prev[0] + di[0]
        for j in range(1, len(r)):
            cur[j] = di[j] + min(prev[j - 1], cur[j - 1], prev[j])
        prev = cur
    return prev


def tile_alerts(last_row, threshold, chunk: int) -> list:
    """A threshold monitor's alerts over a last row fed in ``chunk``-wide
    tiles: ``[(tile_start, hits, distance, end)]`` for every tile with a
    column at or under ``threshold`` — the count of such columns, their
    least cost and the leftmost column attaining it."""
    row = np.asarray(last_row, np.float64)
    out = []
    for t0 in range(0, len(row), chunk):
        cols = np.flatnonzero(row[t0:t0 + chunk] <= threshold)
        if cols.size:
            best = cols[np.argmin(row[t0 + cols])]
            out.append((t0, int(cols.size), float(row[t0 + best]),
                        t0 + int(best)))
    return out
