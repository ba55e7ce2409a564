#!/usr/bin/env python3
"""Offered-load sweep of a served cell, to find its knee once.

    python3 bench/sweep.py --workload human-served --rates 200,300,400 \\
        --seconds 10 --seed 1

Runs the cell's window at each rate in turn, in one process (the cell's
own mix with ``rate_per_s`` replaced), and prints one JSON line per
rate: latency percentiles, refusals, how long the queue took to drain
after the last due request, and the backend compiles inside the window.
A rate is sustained when nothing is refused and the queue drains within
a tenth of a second of the last due request. The cell's ``rate_per_s``
is set once, by hand, to four fifths of the highest sustained rate; the
benchmark never searches for a rate itself. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import run
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    cell = run.load_cell(ROOT, args.workload)
    driver = run.load_module(cell["driver"])
    clock = run.CompileClock()
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell["mix"], rate_per_s=rate)
        drv = driver.Driver(cell["config"], mix, args.seed, args.seconds)
        _, n0, _ = clock.read()
        res = drv.window(args.seconds)
        _, n1, _ = clock.read()
        drv.close()
        notes = res["notes"]
        print(json.dumps({
            "rate_per_s": rate, **res["metrics"],
            "sustained": res["failed"] == 0 and notes["drain_s"] <= 0.1,
            "window_compiles": n1 - n0, "checks": drv.check(),
            **notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
