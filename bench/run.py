#!/usr/bin/env python3
"""Run one benchmark cell once, in one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else is found from its names:

  configs[].file                 the deployment's sizes (``bench/configs``)
  bench/traffic/<traffic>.json   the traffic mix: its ``driver`` and the
                                 driver's parameters
  bench/traffic/<driver>.py      the driver: ``Driver(config, mix, seed,
                                 seconds)`` generates the inputs and warms
                                 up; ``window(seconds)`` drives the program;
                                 ``close()`` frees its state; ``check()``
                                 compares what the window produced with
                                 ``bench/reference.py``
  bench/metrics/<metric>.py      one reader per per-layer metric:
                                 ``read(record)`` returns a number, or
                                 None when the run has nothing to read

A run loads, warms up (both counted in ``setup_s``), measures for
``--seconds``, frees the program's state, checks correctness, and prints
one JSON object as the last line of standard output. With ``--trace 0``
its metrics are the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the JAX profiler and the metrics are the per-layer
ones, read from the trace, the harness's own host spans and the
program's counters. The numbers compared for ``correct`` are printed,
each beside its limit, as the last lines of standard error and under the
result's last key, ``checks``.

Exits 3 and prints no result when JAX finds no TPU or fewer chips than
the cell asks for, and 4 when the program under test cannot be imported.
JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root
of the checkout, whatever the environment says, so that only a cell's
first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# Import the benchmark as the package ``bench`` from the checkout root, so
# that its module names (``trace``, ``data``) never shadow the stdlib's.
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, from its names
# ---------------------------------------------------------------------------

def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, name: str) -> dict:
    """The cell ``name`` with its configuration, traffic mix, driver path
    and the metric entries that apply to it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    mix = json.loads((root / "bench" / "traffic" /
                      f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in
                                  e2e_names else [])]
    return {"name": name, "chips": int(w["chips"]),
            "config": json.loads((root / cfg["file"]).read_text()),
            "mix": mix,
            "driver": root / "bench" / "traffic" / f"{mix['driver']}.py",
            "end_to_end": e2e, "per_layer": per_layer,
            "metrics_dir": root / "bench" / "metrics"}


# ---------------------------------------------------------------------------
# Compile time, from JAX's monitoring events
# ---------------------------------------------------------------------------

#: XLA/Mosaic compile time of each executable (on a persistent-cache hit
#: the event times the cache read) and the cache's hit events.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums backend compile seconds and counts compiles and persistent-
    cache hits, from any thread."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits


def say(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             t_start: float = T_START) -> dict:
    """Run the cell once and return the result line as a dict.

    ``require_tpu=False`` lets the CPU rehearse every step but the look
    for a chip; its timings are then not device numbers."""
    cell = load_cell(root, workload)
    import jax
    t_jax = time.perf_counter()
    devs = jax.devices()
    t_devices = time.perf_counter()
    kind = devs[0].device_kind
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        raise NoAccelerator(
            f"cell {workload!r} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devs)} {devs[0].platform!r} device(s)")
    src = root / "src"
    if src.is_dir() and str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro.core  # noqa: F401  (the program under test)
    clock = CompileClock()
    driver = load_module(cell["driver"])
    t_program = time.perf_counter()
    drv = driver.Driver(cell["config"], cell["mix"], seed, seconds)
    setup_s = time.perf_counter() - t_start
    c_s, c_n, c_hits = clock.read()
    parts = " ".join(f"{k}={v:.3f}" for k, v in drv.setup_parts.items())
    say(f"setup_s={setup_s:.3f}: python_jax_import_s={t_jax - t_start:.3f} "
        f"device_init_s={t_devices - t_jax:.3f} "
        f"program_import_s={t_program - t_devices:.3f} "
        f"{parts} (backend compile {c_s:.3f} s in {c_n} executables, "
        f"{c_hits} persistent-cache hits)")

    from bench import trace as tr
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir, profiler_options=tr.options())
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            res = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    w_s, w_n, _ = clock.read()
    say(f"window: {json.dumps(res.get('notes', {}))}; backend compiles "
        f"inside the window: {w_n - c_n} ({w_s - c_s:.3f} s)")
    used = devs[:getattr(drv, "n_devices", 1)]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    drv.close()

    checks = drv.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if trace:
        try:
            summary = tr.reduce(tdir, n_devices=len(used))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        record = {"trace": summary, "device_kind": kind, "cell": workload,
                  "calls": res.get("calls", []),
                  "counters": res.get("counters", {})}
        metrics = {}
        for m in cell["per_layer"]:
            reader = load_module(cell["metrics_dir"] / f"{m['name']}.py")
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.idle_gaps(10)}
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
        out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoAccelerator as exc:
        say(f"bench: {exc}; nothing was run")
        return 3
    except ImportError as exc:
        say(f"bench: cannot import the program under test: {exc}")
        return 4
    for name, c in out["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
