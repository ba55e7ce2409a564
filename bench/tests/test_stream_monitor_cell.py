"""The many-stream monitoring cell, rehearsed on the CPU at a tiny size:
4 streams, 3 templates of 16, ticks of 40 samples (the Pallas kernel in
interpret mode, the session on the rowscan path the CPU dispatches)."""
import json
import sys

import numpy as np
import pytest

from bench import control, reference_stream, run, stream_loop
from bench.tests.conftest import ROOT

sys.path.insert(0, str(ROOT / "tests"))
from oracle import sdtw_last_row  # noqa: E402

CELL = "ecg-stream-monitor"
TINY = {"streams": 4, "templates": 3, "query_size": 16, "sample_rate_hz": 40}
SECONDS = 3.0


@pytest.fixture
def monitor_root(tiny_root):
    path = tiny_root / "bench" / "configs" / "ecg-monitor.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TINY}))
    return tiny_root


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(monitor_root, trace):
    out = run.run_cell(monitor_root, CELL, 2**31 + 7, SECONDS, trace,
                       require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"dist_wrong", "alert_wrong",
                                  "planted_missed", "unanswered"}
    assert out["attempted"] == 3 and out["failed"] == 0
    spec = run.load_cell(monitor_root, CELL)
    if not trace:
        assert set(out["metrics"]) == {"served_p50_ms", "setup_s"}
        assert set(out["metrics"]) == {m["name"]
                                       for m in spec["end_to_end"]}
        return
    assert set(out["metrics"]) <= {m["name"] for m in spec["per_layer"]}
    # the CPU has no device trace: the program's own numbers only
    assert out["metrics"]["stream.launches_per_tick"]["value"] == 1.0
    assert out["metrics"]["stream.host_ms"]["value"] > 0


def _alter_alerts(how):
    def alter(alerts):
        hits, dist, col, start = alerts[0]
        if how == "alert_altered":
            dist = dist + 1
        else:                                   # every alert of the tile
            hits = hits * 0
        return [(hits, dist, col, start)] + list(alerts[1:])
    return alter


@pytest.mark.parametrize("fault", ["alert_altered", "alert_dropped",
                                   "distance_altered"])
def test_fault_is_not_correct(monitor_root, monkeypatch, fault):
    from repro.stream import StreamSession
    if fault == "distance_altered":
        real = StreamSession.results

        def results(self):
            res = real(self)
            res.distances = np.asarray(res.distances).copy()
            res.distances[0, 0] += 1
            return res

        monkeypatch.setattr(StreamSession, "results", results)
    else:
        real = StreamSession._emit_alerts
        alter = _alter_alerts(fault)
        monkeypatch.setattr(
            StreamSession, "_emit_alerts",
            lambda self, alerts, j0, clen: real(self, alter(alerts), j0,
                                                clen))
    out = run.run_cell(monitor_root, CELL, 11, SECONDS, False,
                       require_tpu=False)
    assert out["correct"] is False, out["checks"]


def test_reference_equals_oracle():
    rng = np.random.default_rng(4)
    q = rng.integers(-40, 40, (3, 9)).astype(np.int32)
    r = rng.integers(-40, 40, (2, 70)).astype(np.int32)
    got = np.asarray(reference_stream.last_rows(q, r, block=4))
    assert got.shape == (2, 3, 70)
    for s in range(2):
        for i in range(3):
            np.testing.assert_array_equal(got[s, i], sdtw_last_row(q[i],
                                                                   r[s]))


def test_control_fails_program_passes(monitor_root):
    """Templates of 64 carry bursts of 32 samples, whose chance matches
    cost past int16's ceiling: the int16 reference's distances differ."""
    path = monitor_root / "bench" / "configs" / "ecg-monitor.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    query_size=64, sample_rate_hz=80)))
    program, ctrl, _ = control.readings(monitor_root, CELL, 3, SECONDS,
                                        require_tpu=False)
    assert all(c["value"] <= c["limit"] for c in program.values()), program
    assert ctrl["dist_wrong"]["value"] > 0, ctrl


def test_stream_loop_rehearses_both_forms():
    out = stream_loop.compare(dict(TINY, alert_threshold=4096), ticks=2,
                              seed=1)
    assert len(out["loop_s_per_tick"]) == len(out["fleet_s_per_tick"]) == 2
    assert out["fleet_counters"]["launches"] == 2
    assert out["fleet_counters"]["streams_advanced"] == 2 * TINY["streams"]
    assert out["loop_alerts"] == out["fleet_alerts"]


def _tick_trace(window, missing):
    """Four ticks a second apart: on the host clock they start at 50-53 s,
    tick i fed i ms late, its launch ending 10 (i + 1) ms into its
    ``stream.tick`` span; on the trace's clock everything sits 1,000 s
    later, the window is ``window`` (seconds on that clock) and the
    ticks in ``missing`` have no spans."""
    from bench import trace
    calls, host = [], []
    for i in range(4):
        t_start = 50.0 + i
        calls.append({"t_start": t_start, "t_due": t_start - i * 1e-3,
                      "ops": 1, "bytes": 1})
        s = int((t_start + 1000.0 + 2e-6) * 1e9)
        if i not in missing:
            host += [("stream.tick", s, s + 50_000_000),
                     ("stream.launch", s + 1_000, s + 10_000_000 * (i + 1))]
    t0, t1 = (int(w * 1e9) for w in window)
    summary = trace.Summary((t0, t1), [trace.Device([], [], [])],
                            [("bench.window", t0, t1), *host])
    return {"trace": summary, "calls": calls, "counters": {},
            "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("window, missing, want", [
    # every tick in the window: 0 + 10, 1 + 20, 2 + 30, 3 + 40 ms
    ((1049.999, 1054.0), (), 26.5),
    # the first tick's spans are missing: ticks 1-3 give 21, 32, 43 ms
    # (paired by order they would give 31)
    ((1049.999, 1054.0), (0,), 32.0),
    # the last tick's span opens after the window: ticks 0-2 give 10, 21,
    # 32 ms
    ((1049.999, 1052.5), (), 21.0),
])
def test_stream_host_ms_pairs_ticks_by_time(window, missing, want):
    read = run.load_module(ROOT / "bench" / "metrics"
                           / "stream.host_ms.py").read
    assert read(_tick_trace(window, missing)) == pytest.approx(want,
                                                               abs=1e-3)


def test_stream_roofline_is_the_kernel_roofline():
    from bench import trace
    dev = trace.Device(["jit__exact_step/sdtw_pallas"], [100], [600])
    rec = {"trace": trace.Summary((0, 1000), [dev], [("bench.window", 0,
                                                      1000)]),
           "calls": [{"ops": 3e6, "bytes": 1e3}], "counters": {},
           "device_kind": "TPU v5 lite"}
    metrics = ROOT / "bench" / "metrics"
    got = run.load_module(metrics / "sdtw_roofline.stream.py").read(rec)
    assert got > 0
    assert got == run.load_module(metrics / "sdtw_roofline.py").read(rec)
