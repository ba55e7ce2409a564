"""``stream.pad_lane_pct`` on the many-stream monitoring cell, rehearsed
on the CPU: the share of each kernel launch's reference lanes that carry
no sample, and nothing read where the program counts no lanes."""
import json

import pytest

from bench import run
from bench.tests.conftest import ROOT
from bench.tests.test_stream_monitor_cell import CELL, SECONDS, TINY

READ = run.load_module(ROOT / "bench" / "metrics"
                       / "stream.pad_lane_pct.py").read


@pytest.fixture
def monitor_root(tiny_root):
    path = tiny_root / "bench" / "configs" / "ecg-monitor.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TINY,
                                "sample_rate_hz": 360}))
    return tiny_root


def test_pad_lane_pct_reads_the_launched_block(monitor_root, monkeypatch):
    """The cell's 360-sample ticks through the kernel at the 384-lane
    block the TPU tuner picks for them (forced here: the CPU session runs
    the rowscan by default) leave 24 of each launch's 384 lanes empty."""
    import repro.core
    real = repro.core.stream
    monkeypatch.setattr(repro.core, "stream", lambda *a, **kw: real(
        *a, impl="pallas", block_m=384, **kw))
    out = run.run_cell(monitor_root, CELL, 2**31 + 11, SECONDS, True,
                       require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["stream.pad_lane_pct"] == {"value": 6.25,
                                                     "unit": "%"}


@pytest.mark.parametrize("counters", [
    {},                                           # a program without them
    {"launches": 3, "lanes_fed": 0, "lanes_launched": 0},  # no kernel
])
def test_pad_lane_pct_reads_nothing_without_kernel_lanes(counters):
    assert READ({"counters": counters}) is None
    assert READ({"counters": {"lanes_fed": 360,
                              "lanes_launched": 512}}) == \
        pytest.approx(29.6875)
