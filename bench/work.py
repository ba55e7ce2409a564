"""Operations and bytes that an sDTW call needs, from its true shapes.

Counted from the recurrence (see ``reference.py``), not from any
kernel's code, so the same call is given the same work whatever
computes it. Per DP cell, in int32 vector operations:

  plain  distance |q - r| (2: subtract, absolute value), least of three
         predecessors (2 minima), add the distance (1), saturate at the
         ceiling (1): 6.
  spans  the plain 6, plus the start of the chosen predecessor with the
         smaller-start tie-break: for each of the three predecessors a
         compare of its value with the least and a select of its start
         (6), and the least of the three selected starts (2): 14.

Cells are nominal: true query length times reference length for every
query, so a later kernel that skips cells reads as faster, not as doing
less work. Bytes are the least any implementation must move through
HBM: the queries and the reference read once, the answers written once.
"""
from __future__ import annotations

import json
from pathlib import Path

OPS_PER_CELL = {"plain": 6, "spans": 14}
WORD = 4                                    # int32

PEAKS = Path(__file__).resolve().with_name("peaks.json")


def sdtw_call(nq: int, n: int, m: int, spans: bool) -> dict:
    """``{"cells", "ops", "bytes"}`` of one call of ``nq`` queries of
    length ``n`` against a reference of ``m`` samples."""
    c = nq * n * m
    outputs = 3 if spans else 1
    return {"cells": c,
            "ops": c * OPS_PER_CELL["spans" if spans else "plain"],
            "bytes": WORD * (nq * n + m + nq * outputs)}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return {k: v["value"] for k, v in table[device_kind].items()}


def roofline(ops: float, nbytes: float, seconds: float,
             device_kind: str) -> tuple:
    """(share of the roofline in %, the bound that applies): the least
    time the chip could take for this work, the larger of the VPU and
    HBM times, over the time it took."""
    pk = peaks(device_kind)
    t_ops = ops / pk["vpu_int32_ops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "vpu" if t_ops >= t_bytes else "hbm"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
