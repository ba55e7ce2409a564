#!/usr/bin/env python3
"""Launch-block sweep of the sDTW kernel on one chip.

    python3 bench/block_sweep.py --out sweep.jsonl \\
        --budget-s 720

Times ``sdtw_pallas`` with explicit ``(block_q, block_m, row_tile)`` at
the paper's Table V query lengths (64, 120, 512, 1536), for several
batch sizes and the kernel's three variants (plain distances, match
spans, span-tracked last-row capture), and prints one JSON line per
configuration: compile seconds, the median call time (each call ends in
``block_until_ready``), real DP cells per second, and the microseconds
one grid tile spends per DP row. Phases run in order of importance:

  cells   the two batch cells' own shapes (Human: 16,384 queries of 120
          against 7,997, plain; ECG: 128 queries of 512 against 262,144,
          spans), each block's answers checked equal to the first's;
  grid    plain and spans at N 120 and 512, block_q 8-256, block_m
          128-1024;
  wide    the same at block_m 2048 and 4096;
  small   one and eight queries per call (the served shape);
  widths  N 64 and 1,536, all three variants;
  lastrow the last-row capture at N 120 and 512.

Each phase compiles its configurations in a thread pool, then times them
one at a time. No phase starts once ``--budget-s`` seconds have passed.
A configuration the compiler refuses is printed with its error. The
lines are the evidence for the TPU constants of
``repro.core.platforms.TPU_V5E_BACKEND``. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

VARIANTS = {"plain": {}, "spans": {"return_spans": True},
            "lastrow": {"return_lastrow": True, "track_start": True}}
#: DP cells per timed call in the grid phases: 12 ms at 2.5e10 cells/s,
#: 120 ms at 2.5e9.
GRID_CELLS = 3e8


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _spec(phase, variant, n, nq, m, bq, bm, rt):
    return dict(phase=phase, variant=variant, n=n, nq=nq, m=m, bq=bq,
                bm=bm, rt=rt)


def _grid_m(nq: int, n: int, bm: int) -> int:
    return _ceil_to(max(int(GRID_CELLS / (nq * n)), 2 * bm), bm)


def phases():
    """``[(name, [spec, ...]), ...]`` in the order they run."""
    cells = []
    for bq, bm, rt in ((8, 256, 8), (64, 256, 8), (64, 256, 2),
                       (128, 256, 2), (128, 512, 2), (256, 256, 2)):
        cells.append(_spec("cells", "plain", 120, 16384, 7997, bq, bm, rt))
        if bq <= 128:
            cells.append(_spec("cells", "spans", 512, 128, 262144, bq, bm,
                               rt))
    grid = []
    for variant in ("plain", "spans"):
        for n in (120, 512):
            for bq in (8, 16, 32, 64, 128, 256):
                for bm in (128, 256, 512, 1024):
                    if bq * bm > 65536:
                        continue
                    nq = max(128, bq)
                    m = _grid_m(nq, n, bm)
                    rts = (2, 8) if bq * bm <= 16384 else (2,)
                    for rt in rts:
                        grid.append(_spec("grid", variant, n, nq, m, bq, bm,
                                          rt))
            for bq in (64, 128):
                grid.append(_spec("grid", variant, 512, 128,
                                  _grid_m(128, 512, 256), bq, 256, 1))
    wide = []
    for variant in ("plain", "spans"):
        for n in (120, 512):
            for bq, bm in ((8, 2048), (8, 4096), (16, 2048), (16, 4096),
                           (32, 2048)):
                wide.append(_spec("wide", variant, n, 128,
                                  _grid_m(128, n, bm), bq, bm, 2))
    small = []
    for nq in (1, 8):
        for bm, rts in ((128, (8,)), (256, (8,)), (512, (2, 8)),
                        (1024, (2, 8)), (2048, (2,)), (4096, (2,))):
            for rt in rts:
                small.append(_spec("small", "plain", 120, nq, 7997, 8, bm,
                                   rt))
    for bm in (256, 1024, 4096):
        small.append(_spec("small", "spans", 512, 1, 65536, 8, bm,
                           8 if bm == 256 else 2))
    widths = []
    for n in (64, 1536):
        for variant in ("plain", "spans", "lastrow"):
            for bq, bm, rt in ((8, 256, 8), (64, 512, 2), (128, 256, 2),
                               (16, 4096, 2), (32, 2048, 2)):
                widths.append(_spec("widths", variant, n, 128,
                                    _grid_m(128, n, bm), bq, bm, rt))
    lastrow = []
    for n in (120, 512):
        for bq, bm, rt in ((8, 256, 8), (64, 512, 2), (16, 4096, 2)):
            lastrow.append(_spec("lastrow", "lastrow", n, 128,
                                 _grid_m(128, n, bm), bq, bm, rt))
    return [("cells", cells), ("grid", grid), ("wide", wide),
            ("small", small), ("widths", widths), ("lastrow", lastrow)]


def _fn(spec, interpret: bool):
    import jax
    from repro.kernels.sdtw import sdtw_pallas
    kw = dict(VARIANTS[spec["variant"]], block_q=spec["bq"],
              block_m=spec["bm"], row_tile=spec["rt"], scan_scheme="shift",
              interpret=interpret, tune="off")
    return jax.jit(lambda q, r: sdtw_pallas(q, r, **kw))


def _inputs(spec, device):
    import jax
    rng = np.random.default_rng(spec["n"] * 7919 + spec["nq"] + spec["m"])
    q = rng.integers(-100, 100, (spec["nq"], spec["n"])).astype(np.int32)
    r = rng.integers(-100, 100, (spec["m"],)).astype(np.int32)
    return jax.device_put(q, device), jax.device_put(r, device)


def _compile(spec, interpret):
    import jax
    q = jax.ShapeDtypeStruct((spec["nq"], spec["n"]), np.int32)
    r = jax.ShapeDtypeStruct((spec["m"],), np.int32)
    t0 = time.perf_counter()
    try:
        exe = _fn(spec, interpret).lower(q, r).compile()
    except Exception as e:                              # noqa: BLE001
        return None, time.perf_counter() - t0, f"{type(e).__name__}: " \
            + " ".join(str(e).split())[:400]
    return exe, time.perf_counter() - t0, None


def _time(exe, q, r, min_total_s: float = 0.3, max_reps: int = 20):
    import jax
    out = jax.block_until_ready(exe(q, r))
    ts = []
    while len(ts) < 2 or (sum(ts) < min_total_s and len(ts) < max_reps):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(q, r))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts), len(ts), out


def run_phase(name, specs, interpret, device, emit, workers=6):
    with ThreadPoolExecutor(workers) as ex:
        compiled = list(ex.map(lambda s: _compile(s, interpret), specs))
    first = {}
    for spec, (exe, compile_s, err) in zip(specs, compiled):
        row = dict(spec, compile_s=round(compile_s, 3))
        if err is not None:
            emit(dict(row, error=err))
            continue
        q, r = _inputs(spec, device)
        try:
            call_s, reps, out = _time(exe, q, r)
        except Exception as e:                          # noqa: BLE001
            emit(dict(row, error=f"{type(e).__name__}: "
                      + " ".join(str(e).split())[:400]))
            continue
        q_tiles = -(-spec["nq"] // spec["bq"])
        m_tiles = -(-max(spec["m"], spec["bm"]) // spec["bm"])
        row.update(call_s=call_s, reps=reps,
                   cells_per_s=spec["nq"] * spec["n"] * spec["m"] / call_s,
                   us_per_tile_row=call_s * 1e6
                   / (q_tiles * m_tiles * spec["n"]))
        if name == "cells":
            key = (spec["variant"], spec["n"], spec["nq"], spec["m"])
            got = [np.asarray(x) for x in
                   (out if isinstance(out, tuple) else (out,))]
            if key not in first:
                first[key] = got
            row["same_answers"] = all(
                np.array_equal(a, b) for a, b in zip(first[key], got))
        emit(row)
        del exe, q, r, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--budget-s", type=float, required=True)
    ap.add_argument("--phases", default=None,
                    help="comma-separated phase names (default: all)")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"block_sweep: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 3
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    wanted = set(args.phases.split(",")) if args.phases else None
    t_start = time.perf_counter()
    with out.open("a") as f:
        def emit(row):
            row = dict(row, device_kind=dev.device_kind)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
        for name, specs in phases():
            if wanted is not None and name not in wanted:
                continue
            if time.perf_counter() - t_start > args.budget_s:
                print(f"block_sweep: budget spent, phase {name} skipped",
                      file=sys.stderr)
                continue
            run_phase(name, specs, False, dev, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
