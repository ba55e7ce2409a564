#!/usr/bin/env python3
"""The control of the correctness check: the reference, computed in a
narrower type, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one process runs the cell's window as a benchmark run
does, reads the program's numbers from ``check()`` (the lower readings),
then replaces every answer of the window with the plain reference's
answer for the same queries computed in int16 (the nearest integer type
below the int32 that the configurations state, saturating at
``INT16_BIG``) and reads the same numbers again (the upper readings).
The limits in ``BENCHMARK.json``'s cells lie between the two. Prints one
JSON line per seed. Needs the chip, like a benchmark run.
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

#: int16's saturation ceiling: two of them still add without overflow.
INT16_BIG = 2 ** 14 - 1


def int16_answers(queries, ref, spans):
    """The reference's answers in int16, shaped as the program's."""
    from bench import reference
    out = reference.sdtw(queries, ref, spans=spans, acc="int16",
                         big=INT16_BIG, block=2048)
    return out if spans else out[:1]


def readings(root, workload, seed, seconds, require_tpu=True):
    """``(program checks, control checks, window notes)`` for one seed."""
    from bench import run
    cell = run.load_cell(root, workload)
    import jax
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise run.NoAccelerator(f"JAX found {dev.platform!r}")
    src = root / "src"
    if src.is_dir() and str(src) not in sys.path:
        sys.path.insert(0, str(src))
    drv = run.load_module(cell["driver"]).Driver(cell["config"], cell["mix"],
                                                seed, seconds)
    res = drv.window(seconds)
    drv.close()
    program = drv.check()
    drv.answer_with(int16_answers)
    return program, drv.check(), res.get("notes", {})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        program, control, notes = readings(ROOT, args.workload, seed,
                                           args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control,
                          "notes": notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
