"""The request-lifecycle spans and the Router's stage split.

Runs a served window and a batch call under ``jax.profiler`` and reads
the written ``.xplane.pb`` back with ``jax.profiler.ProfileData``: the
program's ``serve.*`` and ``engine.*`` spans appear where the request
path takes them, nest as documented in ``repro.serve.telemetry``, and
count what the Router counts. Every check here runs on the CPU.
"""
import collections
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.core.engine as engine
from repro.serve import RequestTrace, Router, RouterConfig, Telemetry

SERVE = ("serve.submit", "serve.window", "serve.group", "serve.execute",
         "serve.deliver")
ENGINE = ("engine.ragged", "engine.prepare", "engine.launch")
STREAM = ("stream.tick", "stream.launch", "stream.alerts")

Span = collections.namedtuple("Span", "line name start end stats")


def _traced(fn):
    """Run ``fn()`` under the profiler; return its result and the spans
    of the program (``serve.*``, ``engine.*``, ``stream.*``) on the host
    plane."""
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = Path(tdir).rglob("*.xplane.pb")
        pd = jax.profiler.ProfileData.from_file(str(path))
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SERVE + ENGINE + STREAM:
                    spans.append(Span(i, ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return out, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(inner, outer):
    return (inner.line == outer.line and outer.start <= inner.start
            and inner.end <= outer.end)


def _requests(rng, count, n=12, m=96):
    r = rng.integers(-40, 40, m).astype(np.int32)
    qs = [rng.integers(-40, 40, (1, n)).astype(np.int32)
          for _ in range(count)]
    return qs, r


def _serve_one_by_one(qs, r):
    """An auto-dispatching Router answering one request at a time, so
    each group holds one request (no ``engine.ragged``)."""
    with Router(RouterConfig(window_ms=0.05, max_window_requests=1)) as rt:
        before = rt.stats()
        answers = [np.asarray(rt.submit(queries=q, reference=r)
                              .result(timeout=60)) for q in qs]
        after = rt.stats()
    return answers, before, after


@pytest.fixture(scope="module")
def served():
    qs, r = _requests(np.random.default_rng(7), 4)
    (answers, before, after), spans = _traced(
        lambda: _serve_one_by_one(qs, r))
    return qs, r, answers, before, after, spans


@pytest.fixture(scope="module")
def coalesced():
    """One manual drain of requests of three lengths: a merged group
    whose engine call is ragged, with one bucket per length class."""
    rng = np.random.default_rng(11)
    r = rng.integers(-40, 40, 96).astype(np.int32)
    qs = [rng.integers(-40, 40, (1, n)).astype(np.int32)
          for n in (12, 20, 40)]

    def run():
        rt = Router(RouterConfig(auto_dispatch=False))
        futs = [rt.submit(queries=q, reference=r) for q in qs]
        rt.drain()
        rt.close()
        return [np.asarray(f.result(timeout=0)) for f in futs], rt.stats()

    (answers, stats), spans = _traced(run)
    return qs, r, answers, stats, spans


def test_served_path_emits_every_span(served):
    *_, spans = served
    names = {s.name for s in spans}
    assert set(SERVE) | {"engine.prepare", "engine.launch"} <= names
    assert "engine.ragged" not in names       # one-request groups


def test_engine_spans_fall_inside_serve_execute(served):
    *_, spans = served
    execs = _named(spans, "serve.execute")
    for s in spans:
        if s.name.startswith("engine.") or s.name == "serve.deliver":
            assert any(_inside(s, e) for e in execs), s


def test_execute_spans_count_dispatches(served):
    qs, _, _, before, after, spans = served
    assert len(_named(spans, "serve.execute")) == \
        after.dispatches - before.dispatches == len(qs)
    assert len(_named(spans, "engine.launch")) == len(qs)


def test_spans_carry_the_request_id(served):
    """One request's spans on the client, dispatcher and pool threads
    share its ``req`` stat."""
    qs, *_, spans = served
    for name in ("serve.submit", "serve.group", "serve.execute",
                 "serve.deliver"):
        # ``close()`` drains an empty queue: a group span with no request
        ids = sorted(s.stats["req"] for s in _named(spans, name)
                     if "req" in s.stats)
        assert ids == list(range(1, len(qs) + 1)), name
    for e in _named(spans, "serve.execute"):
        assert e.stats["requests"] == 1 and e.stats["queries"] == 1


def test_stage_means_add_up_to_latency(served):
    *_, after, _ = served
    assert after.completed == 4 and after.errors == 0
    stages = (after.mean_admit_wait_us, after.mean_pool_wait_us,
              after.mean_engine_us, after.mean_deliver_us)
    assert all(x >= 0 for x in stages)
    assert after.mean_engine_us > 0
    assert sum(stages) == pytest.approx(after.mean_latency_us, rel=1e-9)


def test_answers_unchanged_with_profiler_off(served):
    qs, r, traced_answers, *_ = served
    untraced, _, _ = _serve_one_by_one(qs, r)
    for q, a, b in zip(qs, traced_answers, untraced):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, np.asarray(engine.sdtw(q, r)))


def test_ragged_group_nests_per_bucket_spans(coalesced):
    qs, r, answers, stats, spans = coalesced
    assert stats.dispatches == 1
    ragged, = _named(spans, "engine.ragged")
    execute, = _named(spans, "serve.execute")
    assert _inside(ragged, execute)
    assert execute.stats["requests"] == len(qs)
    buckets = len(engine.bucketize([q.shape[1] for q in qs]))
    for name in ("engine.prepare", "engine.launch"):
        got = _named(spans, name)
        assert len(got) == buckets > 1
        assert all(_inside(s, ragged) for s in got)
    for q, a in zip(qs, answers):
        np.testing.assert_array_equal(a, np.asarray(engine.sdtw(q, r)))


@pytest.mark.parametrize("fixture", ["served", "coalesced"])
def test_no_span_nests_inside_itself(fixture, request):
    spans = request.getfixturevalue(fixture)[-1]
    for a in spans:
        for b in spans:
            assert a is b or a.name != b.name or not _inside(a, b), a


def test_batch_call_gives_one_prepare_and_one_launch(rng):
    q = rng.integers(-40, 40, (5, 16)).astype(np.int32)
    r = rng.integers(-40, 40, 128).astype(np.int32)
    out, spans = _traced(lambda: np.asarray(engine.sdtw(q, r)))
    assert [s.name for s in sorted(spans, key=lambda s: s.start)] == \
        ["engine.prepare", "engine.launch"]
    prepare, launch = sorted(spans, key=lambda s: s.start)
    assert prepare.end <= launch.start
    assert launch.stats["nq"] == 5
    np.testing.assert_array_equal(out, np.asarray(engine.sdtw(q, r)))


def test_stage_split_of_one_trace():
    t = RequestTrace(op="sdtw", nq=1, t_enqueue=1.0)
    t.t_drain, t.t_dispatch, t.t_launched, t.t_complete = 1.5, 3.0, 3.25, 4
    assert t.stages_us() == (0.5e6, 1.5e6, 0.25e6, 0.75e6)


def test_skipped_stage_reads_zero_and_stages_still_add_up():
    """An engine call that raised stamps no ``t_launched``: its stage
    takes the time up to completion and delivery reads 0."""
    tel = Telemetry()
    t = RequestTrace(op="sdtw", nq=1, t_enqueue=1.0)
    t.t_drain, t.t_dispatch, t.t_complete = 1.5, 3.0, 4.0
    assert t.stages_us() == (0.5e6, 1.5e6, 1e6, 0.0)
    tel.record_complete(t)
    snap = tel.snapshot()
    assert snap.mean_engine_us == 1e6 and snap.mean_deliver_us == 0
    assert (snap.mean_admit_wait_us + snap.mean_pool_wait_us
            + snap.mean_engine_us + snap.mean_deliver_us
            == snap.mean_latency_us)


def test_failed_group_is_accounted(rng):
    """A request whose engine call raises is answered with the error and
    its stages still add up to its latency."""
    q = rng.integers(-40, 40, (1, 12)).astype(np.int32)
    r = rng.integers(-40, 40, 96).astype(np.int32)
    with Router(RouterConfig(auto_dispatch=False)) as rt:
        fut = rt.submit(queries=q, reference=r, impl="pallas", excl_lo=0,
                        excl_hi=4)
        rt.drain()
        with pytest.raises(ValueError, match="exclusion"):
            fut.result(timeout=0)
        snap = rt.stats()
    assert snap.errors == 1
    assert (snap.mean_admit_wait_us + snap.mean_pool_wait_us
            + snap.mean_engine_us + snap.mean_deliver_us) == \
        pytest.approx(snap.mean_latency_us, rel=1e-9)


@pytest.mark.parametrize("impl", ["rowscan", "pallas"])
def test_stream_tick_spans_and_counters(impl, rng):
    """Each ``feed`` of a many-stream session is one ``stream.tick``;
    inside it, one ``stream.launch`` per tile advances every stream, and
    one ``stream.alerts`` per tile fetches the tile's alerts after its
    launch. The session's counters count the same."""
    q = rng.integers(-40, 40, (3, 12)).astype(np.int32)
    data = rng.integers(-40, 40, (2, 64)).astype(np.int32)
    data[1, 20:32] = q[2]
    s = engine.stream(q, streams=2, chunk=16, impl=impl,
                      alert_threshold=0)

    def feed():
        s.feed(data[:, :40])        # two whole tiles, 8 samples buffered
        s.feed(data[:, 40:])        # two more tiles
        return s.counters()

    counters, spans = _traced(feed)
    ticks = sorted(_named(spans, "stream.tick"), key=lambda x: x.start)
    launches = _named(spans, "stream.launch")
    fetches = _named(spans, "stream.alerts")
    assert len(ticks) == 2 and len(launches) == len(fetches) == 4
    assert [sum(_inside(x, t) for x in launches) for t in ticks] == [2, 2]
    for f in fetches:
        assert any(_inside(f, t) for t in ticks)
        assert any(x.end <= f.start for x in launches)
    # four 16-sample tiles; the kernel runs each as one 16-lane block
    lanes = 4 * 16 if impl == "pallas" else 0
    assert counters == {"launches": 4, "streams_advanced": 8, "tiles": 4,
                        "alerts": 1, "lanes_fed": lanes,
                        "lanes_launched": lanes}
    ev, = s.alerts
    assert (ev.stream, ev.query, ev.distance, ev.end) == (1, 2, 0, 31)
