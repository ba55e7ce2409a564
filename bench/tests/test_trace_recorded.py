"""The reduction of a trace recorded on one TPU v5e: three plain sDTW
calls of 16 queries of 120 against 2,048 samples, each a ``bench.engine``
span (the call) and a ``bench.fetch`` span (answers to the host), inside
``bench.window``, with the profiler options of ``trace.options()``."""
from pathlib import Path

import pytest

from bench import run, trace

DATA = Path(__file__).with_name("data") / "small.xplane.pb"
ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(DATA)


def test_window_busy_and_kernel(summary):
    kernel = run.load_module(ROOT / "bench" / "metrics" /
                             "sdtw_roofline.py").KERNEL
    assert summary.window_s == pytest.approx(0.009671277)
    assert summary.busy_s() == pytest.approx(0.003815246)
    assert summary.op_seconds(kernel) == pytest.approx(0.003808987)
    assert summary.top_ops(1)[0][0] == "jit_sdtw_pallas/sdtw_pallas"


def test_gaps_named_by_bench_spans(summary):
    gaps = summary.idle_gaps(3)
    assert [g[0] for g in gaps[:2]] == ["bench.fetch", "bench.fetch"]
    assert sum(g[1] for g in summary.idle_gaps(100)) == pytest.approx(
        summary.window_s - summary.busy_s())
