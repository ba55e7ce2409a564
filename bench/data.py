"""Inputs from the seed: the signal model and the planted queries.

The generator is the Table V stand-in the program's examples use (a
smooth two-tone base, Gaussian noise and sparse anomaly bursts, cast to
int32), copied here so that the yardstick cannot drift with the program.
Planted queries are cut from the reference at known offsets, so their
answer is known without any DP: distance 0 on exactly that span.
"""
from __future__ import annotations

import numpy as np


def synthetic_timeseries(rng: np.random.Generator, size: int,
                         anomaly_rate: float = 0.01) -> np.ndarray:
    """Smooth base signal + noise + sparse anomaly bursts, int32."""
    t = np.arange(size)
    base = (1000 * np.sin(2 * np.pi * t / 97.0)
            + 400 * np.sin(2 * np.pi * t / 31.0)
            + rng.normal(0, 20, size))
    n_anom = max(1, int(size * anomaly_rate / 64))
    starts = rng.integers(0, max(1, size - 64), n_anom)
    for s in starts:
        base[s:s + 64] += rng.normal(0, 800, min(64, size - s))
    return base.astype(np.int32)


def plant_offsets(rng, ref, n, count):
    """``count`` distinct offsets whose cut ``ref[o:o+n]`` has a unique
    zero-cost span: the sample before it differs from its first (else the
    smallest-start rule would widen the span by one), and its last two
    samples differ (else a zero-cost path could end one column earlier,
    and the leftmost-end rule would report that end)."""
    out = []
    while len(out) < count:
        o = int(rng.integers(1, ref.shape[0] - n))
        if (ref[o - 1] != ref[o] and ref[o + n - 2] != ref[o + n - 1]
                and all(abs(o - p) > n for p in out)):
            out.append(o)
    return np.asarray(out, np.int64)


def make_batch(rng, ref, n, nq, planted):
    """(queries (nq, n) int32, planted offsets): the first ``planted`` rows
    are cut from ``ref``; the rest from an independent series of the same
    generator."""
    offs = plant_offsets(rng, ref, n, planted)
    other = synthetic_timeseries(rng, max(4 * n, min(8 * nq, 1 << 20)))
    starts = rng.integers(0, other.shape[0] - n, nq - planted)
    q = np.empty((nq, n), np.int32)
    q[:planted] = ref[offs[:, None] + np.arange(n)]
    q[planted:] = other[starts[:, None] + np.arange(n)]
    return q, offs
