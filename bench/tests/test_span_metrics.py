"""The readers of the program's spans, on traces whose answers are known:
spans and operations placed by hand in a window of [100, 1100) ns."""
from pathlib import Path

import pytest

from bench import run, trace

ROOT = Path(__file__).resolve().parents[2]


def _reader(name):
    return run.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _rec(host, ops=()):
    dev = trace.Device([n for n, _, _ in ops], [s for _, s, _ in ops],
                       [e for _, _, e in ops])
    summary = trace.Summary((100, 1100), [dev],
                            [("bench.window", 100, 1100), *host])
    return {"trace": summary, "calls": [], "counters": {},
            "device_kind": "TPU v5 lite"}


SERVED = [
    # starts before the window: not counted
    ("serve.execute", 50, 150), ("engine.launch", 60, 140),
    # two groups in the window
    ("serve.execute", 200, 400), ("engine.prepare", 210, 230),
    ("engine.launch", 230, 290), ("serve.deliver", 300, 390),
    ("serve.execute", 600, 700), ("engine.prepare", 610, 620),
    ("engine.launch", 620, 660),
    # starts in the window, ends after it: clipped
    ("serve.execute", 1000, 1300), ("engine.prepare", 1010, 1020),
    ("engine.launch", 1020, 1060), ("engine.prepare", 1200, 1210),
]


def test_engine_host_ms_serve():
    # (20 + 60) + (10 + 40) + (10 + 40 + 10) ns over the three groups
    # that start in the window; spans inside a group count whole
    got = _reader("engine.host_ms.serve")(_rec(SERVED))
    assert got == pytest.approx((80 + 50 + 60) / 3 / 1e6)


def test_worker_busy_pct():
    # [200, 400), [600, 700) and [1000, 1100) of a 1000 ns window
    got = _reader("serve.worker_busy_pct")(_rec(SERVED))
    assert got == pytest.approx(40.0)


def test_worker_busy_pct_counts_overlap_once():
    host = [("serve.execute", 200, 400), ("serve.execute", 300, 500)]
    assert _reader("serve.worker_busy_pct")(_rec(host)) == \
        pytest.approx(30.0)


def test_kernel_ms_per_launch():
    ops = [("jit_sdtw_pallas/sdtw_pallas", 300, 500),
           ("jit_sdtw_pallas/sdtw_pallas", 1050, 1150),   # clipped to 50
           ("jit_sdtw_pallas/copy", 500, 900)]
    got = _reader("kernel.ms_per_launch.serve")(_rec(SERVED, ops))
    assert got == pytest.approx(1e3 * 250e-9 / 3)


def test_engine_host_ms_batch():
    host = [("bench.engine", 50, 90), ("engine.prepare", 55, 60),
            ("bench.engine", 200, 300), ("engine.prepare", 200, 210),
            ("engine.launch", 210, 240),
            ("bench.engine", 400, 500), ("engine.prepare", 400, 405),
            ("engine.launch", 405, 415),
            ("bench.engine", 600, 700), ("engine.prepare", 600, 650),
            ("engine.launch", 650, 700),
            ("engine.launch", 800, 810)]                # outside any call
    got = _reader("engine.host_ms.batch")(_rec(host))
    assert got == pytest.approx(40 / 1e6)             # median of 40, 15, 100


@pytest.mark.parametrize("name", ["engine.host_ms.serve",
                                  "serve.worker_busy_pct",
                                  "kernel.ms_per_launch.serve",
                                  "engine.host_ms.batch"])
def test_nothing_to_read(name):
    """A program without the spans (the harness's own spans and the
    kernel alone) reads None."""
    host = [("bench.engine", 200, 300), ("bench.fetch", 300, 400),
            ("bench.submit", 500, 510), ("bench.receive", 510, 600)]
    ops = [("jit_sdtw_pallas/sdtw_pallas", 210, 290)]
    assert _reader(name)(_rec(host, ops)) is None
    assert _reader(name)(_rec([])) is None
