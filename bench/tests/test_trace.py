"""The trace reduction, on intervals whose answers are known."""
import re

import pytest

from bench import trace


def _summary():
    ops = trace.Device(["m/a", "m/b", "m/a"], [10, 15, 50], [20, 30, 60])
    host = [("bench.window", 0, 100), ("bench.engine", 25, 52),
            ("bench.fetch", 55, 95)]
    return trace.Summary((0, 100), [ops], host)


def test_busy_idle_and_op_time():
    s = _summary()
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s() == pytest.approx(30e-9)      # [10, 30] and [50, 60]
    assert s.idle_pct() == pytest.approx(70.0)
    assert s.op_seconds(re.compile(r"/a$")) == pytest.approx(20e-9)
    assert s.top_ops(1) == [["m/a", pytest.approx(20e-9)]]


def test_idle_gaps_named_by_host_activity():
    gaps = _summary().idle_gaps(3)
    assert [g[0] for g in gaps] == ["bench.fetch", "bench.engine",
                                    "no host event"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 20e-9, 10e-9])


def test_window_clips_ops():
    ops = trace.Device(["m/a"], [-50], [50])
    s = trace.Summary((0, 100), [ops], [])
    assert s.busy_s() == pytest.approx(50e-9)


def test_op_names():
    assert trace._op_base("%sdtw_pallas.1 = (s32[8,1]) custom-call()") == \
        "sdtw_pallas"
    assert trace._op_base("%fusion.12.3 = s32[] add()") == "fusion"
