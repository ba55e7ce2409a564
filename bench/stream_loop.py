#!/usr/bin/env python3
"""A monitoring deployment's ticks through one session per stream, and
through one session over all streams: what stacking the streams buys.

    python3 bench/stream_loop.py --config bench/configs/ecg-monitor.json \\
        --ticks 4 --seed 5

Not a cell: it draws the configuration's streams and anomaly templates
(as ``bench/traffic/stream_ticks.py`` does, without planted copies),
warms both forms up, then feeds ``--ticks`` one-second ticks
(``sample_rate_hz`` samples) of every stream, first
to one ``stream(...)`` session per stream in a loop, then to one
``stream(..., streams=S)`` session, and prints one JSON line: the
seconds each tick took in each form (every stream's alerts on the host),
the launch block the tuner picks for each form's call, and the alert
counts. Needs the chip for its times; on the CPU it rehearses.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)


def compare(config: dict, ticks: int, seed: int) -> dict:
    from repro.core import stream
    from repro.kernels.sdtw import interpret_mode
    from repro.kernels.sdtw.ops import resolve_blocks

    from bench import data
    from bench.traffic.stream_ticks import templates

    ns, nq = int(config["streams"]), int(config["templates"])
    n, tick = int(config["query_size"]), int(config["sample_rate_hz"])
    thr = int(config["alert_threshold"])
    rng = np.random.default_rng(seed)
    tmpl = templates(rng, nq, n)
    xs = data.synthetic_timeseries(rng, ns * tick * ticks).reshape(ns, -1)

    def blocks(rows, rowref):
        return resolve_blocks(rows, tick, None, None, None, None,
                              interpret_mode(), n=n, tune="model",
                              lastrow=True, rowref=rowref)

    def at(t):
        return xs[:, t * tick:(t + 1) * tick]

    def timed(feed):
        out = []
        for t in range(ticks):
            t0 = time.perf_counter()
            feed(at(t))
            out.append(time.perf_counter() - t0)
        return out

    def open_one(**kw):
        return stream(tmpl, chunk=tick, alert_threshold=thr, **kw)

    warm = open_one()
    warm.feed(at(0)[0]).feed(at(0)[0])
    loop = [open_one() for _ in range(ns)]
    loop_s = timed(lambda x: [s.feed(row) for s, row in zip(loop, x)])
    loop_alerts = sum(len(s.alerts) for s in loop)
    del loop, warm
    open_one(streams=ns).feed(at(0)).feed(at(0))
    fleet = open_one(streams=ns)
    fleet_s = timed(fleet.feed)
    return {"streams": ns, "templates": nq, "tick": tick,
            "loop_block": blocks(nq, False),
            "fleet_block": blocks(ns * nq, True),
            "loop_s_per_tick": loop_s, "fleet_s_per_tick": fleet_s,
            "loop_alerts": loop_alerts, "fleet_alerts": len(fleet.alerts),
            "fleet_counters": fleet.counters()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--ticks", type=int, default=4)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    config = json.loads(Path(args.config).read_text())
    out = compare(config, args.ticks, args.seed)
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
