"""The serving tier: router coalescing, backpressure, session pool.

The load-bearing gate: for any interleaving of concurrent clients, the
router's answers are bitwise int32-identical to offline engine calls —
coalescing into shared bucket dispatches, device pooling, priority
scheduling, and in-window dedup must all be invisible to every tenant.
"""
import concurrent.futures
import threading
import time

import numpy as np
import pytest

import repro.core.engine as engine
from repro.search import search_topk
from repro.serve import (AdmissionQueue, DevicePool, QueueFull, Router,
                         RouterConfig, StreamSessionPool, Telemetry)


def _mk(rng, nq, n, m=300):
    q = rng.integers(-40, 40, (nq, n)).astype(np.int32)
    r = rng.integers(-40, 40, m).astype(np.int32)
    return q, r


# ---------------------------------------------------------------------------
# coalescing == offline, bitwise
# ---------------------------------------------------------------------------

def test_coalesced_window_equals_offline_per_client(rng):
    """One drained window of compatible requests becomes ONE dispatch,
    and every client's slice equals its own offline call bitwise."""
    r = rng.integers(-40, 40, 300).astype(np.int32)
    clients = [rng.integers(-40, 40, (nq, 12)).astype(np.int32)
               for nq in (2, 3, 1, 4)]
    router = Router(RouterConfig(auto_dispatch=False))
    futs = [router.submit(queries=q, reference=r, top_k=2, excl_zone=4,
                          return_spans=True) for q in clients]
    assert router.drain() == len(clients)
    stats = router.stats()
    assert stats.dispatches == 1
    assert stats.mean_batch_requests == len(clients)
    for q, f in zip(clients, futs):
        want = engine.sdtw(q, r, top_k=2, excl_zone=4, return_spans=True)
        got = f.result(timeout=0)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    router.close()


def test_concurrent_clients_bitwise_and_counted(rng):
    """Real threads through the auto-dispatching router: every client
    sees its offline answer, and the stats count every request."""
    r = rng.integers(-40, 40, 256).astype(np.int32)
    clients = [rng.integers(-40, 40, (2, 10)).astype(np.int32)
               for _ in range(6)]
    results = [None] * len(clients)
    with Router(window_ms=5.0) as router:
        def worker(i):
            results[i] = router.sdtw(clients[i], r)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(clients))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = router.stats()
    assert stats.completed == len(clients)
    assert stats.errors == 0
    assert stats.dispatches <= len(clients)
    for q, got in zip(clients, results):
        np.testing.assert_array_equal(np.asarray(engine.sdtw(q, r)),
                                      np.asarray(got))


def test_single_query_clients_unwrap_like_offline(rng):
    """1-D clients coalesce too and still get scalar-shaped answers."""
    r = rng.integers(-40, 40, 200).astype(np.int32)
    qs = [rng.integers(-40, 40, n).astype(np.int32) for n in (7, 12, 9)]
    router = Router(RouterConfig(auto_dispatch=False))
    futs = [router.submit(queries=q, reference=r) for q in qs]
    router.drain()
    assert router.stats().dispatches == 1
    for q, f in zip(qs, futs):
        want = engine.sdtw(q, r)
        got = f.result(timeout=0)
        assert np.asarray(got).shape == np.asarray(want).shape == ()
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    router.close()


def test_search_coalescing_equals_offline_batched(rng):
    """Merged search requests equal ONE offline batched search_topk over
    the concatenated queries (the LB thresholds are batch-shared by
    design — same semantics as calling the batch offline)."""
    r = rng.integers(-40, 40, 600).astype(np.int32)
    qa = [rng.integers(-40, 40, 16).astype(np.int32) for _ in range(2)]
    qb = [rng.integers(-40, 40, 16).astype(np.int32) for _ in range(3)]
    router = Router(RouterConfig(auto_dispatch=False))
    fa = router.submit(queries=qa, reference=r, op="search_topk", top_k=2,
                       ref_key="feed")
    fb = router.submit(queries=qb, reference=r, op="search_topk", top_k=2,
                       ref_key="feed")
    router.drain()
    assert router.stats().dispatches == 1
    want = search_topk(qa + qb, r, 2, ref_key="feed", cache=router.cache)
    merged_d = np.concatenate([np.asarray(fa.result(timeout=0).distances),
                               np.asarray(fb.result(timeout=0).distances)])
    np.testing.assert_array_equal(merged_d, np.asarray(want.distances))
    router.close()


def test_incompatible_requests_do_not_coalesce(rng):
    """Different semantics (metric) or different references must split
    into separate dispatches."""
    q, r = _mk(rng, 2, 8)
    r2 = rng.integers(-40, 40, 300).astype(np.int32)
    router = Router(RouterConfig(auto_dispatch=False))
    f1 = router.submit(queries=q, reference=r)
    f2 = router.submit(queries=q, reference=r, metric="square_diff")
    f3 = router.submit(queries=q, reference=r2)
    router.drain()
    assert router.stats().dispatches == 3
    np.testing.assert_array_equal(np.asarray(f1.result(timeout=0)),
                                  np.asarray(engine.sdtw(q, r)))
    np.testing.assert_array_equal(
        np.asarray(f2.result(timeout=0)),
        np.asarray(engine.sdtw(q, r, metric="square_diff")))
    np.testing.assert_array_equal(np.asarray(f3.result(timeout=0)),
                                  np.asarray(engine.sdtw(q, r2)))
    router.close()


def test_per_query_exclusion_arrays_never_coalesce(rng):
    """Array-valued exclusion zones are sized to one request's batch —
    even two clients sharing the array object must dispatch separately
    (and still match offline bitwise)."""
    r = rng.integers(-40, 40, 200).astype(np.int32)
    q1 = rng.integers(-40, 40, (2, 8)).astype(np.int32)
    q2 = rng.integers(-40, 40, (2, 8)).astype(np.int32)
    lo, hi = np.array([3, 5]), np.array([9, 12])
    router = Router(RouterConfig(auto_dispatch=False))
    f1 = router.submit(queries=q1, reference=r, excl_lo=lo, excl_hi=hi)
    f2 = router.submit(queries=q2, reference=r, excl_lo=lo, excl_hi=hi)
    router.drain()
    assert router.stats().dispatches == 2
    for q, f in ((q1, f1), (q2, f2)):
        np.testing.assert_array_equal(
            np.asarray(f.result(timeout=0)),
            np.asarray(engine.sdtw(q, r, excl_lo=lo, excl_hi=hi)))
    router.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_backpressure_reject_policy(rng):
    q, r = _mk(rng, 1, 6)
    router = Router(RouterConfig(max_queue=2, admission="reject",
                                 auto_dispatch=False))
    router.submit(queries=q, reference=r)
    router.submit(queries=q, reference=r)
    with pytest.raises(QueueFull, match="full"):
        router.submit(queries=q, reference=r)
    assert router.stats().rejected == 1
    router.drain()
    assert router.stats().completed == 2
    router.close()


def test_backpressure_block_timeout(rng):
    q, r = _mk(rng, 1, 6)
    router = Router(RouterConfig(max_queue=1, admission="block",
                                 block_timeout_s=0.05, auto_dispatch=False))
    router.submit(queries=q, reference=r)
    with pytest.raises(QueueFull, match="blocking"):
        router.submit(queries=q, reference=r)
    router.drain()
    router.close()


def test_invalid_requests_refused_at_the_door(rng):
    """Validation runs at submit — the front-door message, raised
    synchronously, nothing enqueued."""
    q, r = _mk(rng, 2, 6)
    router = Router(RouterConfig(auto_dispatch=False))
    with pytest.raises(ValueError) as served:
        router.submit(queries=q, reference=r, excl_lo=5)
    with pytest.raises(ValueError) as offline:
        engine.sdtw(q, r, excl_lo=5)
    assert str(served.value) == str(offline.value)
    with pytest.raises(ValueError, match="unknown SdtwRequest argument"):
        router.submit(queries=q, reference=r, topk=2)
    assert router.drain() == 0
    router.close()


def test_execution_errors_propagate_to_every_member(rng):
    """A failure inside a merged dispatch answers every client future
    instead of hanging the window (admitted == answered)."""
    q, r = _mk(rng, 2, 8)
    router = Router(RouterConfig(auto_dispatch=False))
    bad = np.zeros((2, 2, 2), np.int32)       # 3-D queries explode in run()
    f1 = router.submit(queries=bad, reference=r)
    router.drain()
    with pytest.raises(Exception):
        f1.result(timeout=0)
    assert router.stats().errors == 1
    router.close()


# ---------------------------------------------------------------------------
# shared state across tenants
# ---------------------------------------------------------------------------

def test_envelope_cache_shared_across_tenants(rng):
    q, r = _mk(rng, 2, 16, m=600)
    router = Router(RouterConfig(auto_dispatch=False))
    for _ in range(2):
        f = router.submit(queries=q, reference=r, op="search_topk",
                          top_k=1, ref_key="shared-feed")
        router.drain()
        f.result(timeout=0)
    assert router.cache.hits >= 1
    router.close()


def test_session_pool_churn_and_snapshot_restore(rng):
    ref = rng.integers(-40, 40, 512).astype(np.int32)
    qa = rng.integers(-40, 40, (2, 16)).astype(np.int32)
    qb = rng.integers(-40, 40, (3, 16)).astype(np.int32)
    qc = rng.integers(-40, 40, (1, 16)).astype(np.int32)

    pool = StreamSessionPool()
    pool.attach("feed", "a", queries=qa, chunk=64, top_k=2)
    pool.attach("feed", "b", queries=qb, chunk=64, top_k=2)
    for i in range(0, 256, 128):
        assert pool.feed("feed", ref[i:i + 128]) == 2

    # churn: attach mid-feed → fresh start (only sees the suffix);
    # detach mid-feed → prefix-only results, feed keeps flowing.
    pool.attach("feed", "c", queries=qc, chunk=64, top_k=2)
    with pytest.raises(ValueError, match="already attached"):
        pool.attach("feed", "a", queries=qa, chunk=64)
    res_b = pool.detach("feed", "b")
    db, _ = engine.sdtw(qb, ref[:256], top_k=2, chunk=64)
    np.testing.assert_array_equal(np.asarray(res_b.distances),
                                  np.asarray(db))

    snaps = pool.snapshot("feed")
    assert sorted(snaps) == ["a", "c"]

    pool.feed("feed", ref[256:])
    live = pool.finalize("feed")

    # the restored pool continues bit-for-bit on the same suffix
    pool.restore("feed-replay", snaps)
    pool.feed("feed-replay", ref[256:])
    replay = pool.finalize("feed-replay")
    for t in ("a", "c"):
        np.testing.assert_array_equal(np.asarray(live[t].distances),
                                      np.asarray(replay[t].distances))

    da, _ = engine.sdtw(qa, ref, top_k=2, chunk=64)
    np.testing.assert_array_equal(np.asarray(live["a"].distances),
                                  np.asarray(da))
    dc, _ = engine.sdtw(qc, ref[256:], top_k=2, chunk=64)
    np.testing.assert_array_equal(np.asarray(live["c"].distances),
                                  np.asarray(dc))


# ---------------------------------------------------------------------------
# lifecycle regressions: once admitted, always answered
# ---------------------------------------------------------------------------

def test_close_without_drain_fails_queued_futures(rng):
    """close(drain=False) must fail still-queued futures instead of
    orphaning them (clients blocked in .result() used to hang forever)."""
    q, r = _mk(rng, 2, 8)
    router = Router(RouterConfig(auto_dispatch=False))
    futs = [router.submit(queries=q, reference=r) for _ in range(3)]
    router.close(drain=False)
    for f in futs:
        with pytest.raises(RuntimeError,
                           match="router closed before dispatch"):
            f.result(timeout=1.0)
    stats = router.stats()
    assert stats.unserved_on_close == 3
    assert stats.completed == 0


def test_cancelled_future_does_not_poison_group(rng):
    """A client-cancelled future must not convert its groupmates'
    successes into errors (set_result on a cancelled future used to
    raise InvalidStateError out of the delivery loop)."""
    r = rng.integers(-40, 40, 300).astype(np.int32)
    clients = [rng.integers(-40, 40, (2, 10)).astype(np.int32)
               for _ in range(3)]
    router = Router(RouterConfig(auto_dispatch=False))
    futs = [router.submit(queries=q, reference=r) for q in clients]
    assert futs[1].cancel()
    router.drain()
    for i in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(futs[i].result(timeout=0)),
            np.asarray(engine.sdtw(clients[i], r)))
    stats = router.stats()
    assert stats.cancelled == 1
    assert stats.errors == 0
    assert stats.completed == 2
    router.close()


def test_cancelled_mid_window_under_load(rng):
    """Cancel racing a live dispatch window: every non-cancelled future
    still resolves with its bitwise offline answer."""
    r = rng.integers(-40, 40, 256).astype(np.int32)
    clients = [rng.integers(-40, 40, (1, 8 + i)).astype(np.int32)
               for i in range(8)]
    with Router(window_ms=20.0) as router:
        futs = [router.submit(queries=q, reference=r) for q in clients]
        cancelled = [f.cancel() for f in futs[::2]]
        for i, f in enumerate(futs):
            if i % 2 == 0 and cancelled[i // 2]:
                assert f.cancelled()
                continue
            np.testing.assert_array_equal(
                np.asarray(f.result(timeout=30.0)),
                np.asarray(engine.sdtw(clients[i], r)))


def test_telemetry_bounded_ring():
    """The percentile stores are ring buffers (no unbounded growth);
    counters and means stay exact over the whole lifetime."""
    from repro.serve import RequestTrace
    tel = Telemetry(window=16)
    for _ in range(100):
        t = RequestTrace(op="sdtw", nq=2)
        t.mark_dispatch(batch_requests=1, batch_queries=2)
        t.mark_complete()
        tel.record_complete(t)
    snap = tel.snapshot()
    assert snap.completed == 100
    assert snap.queries_served == 200
    assert snap.latency_samples == 16          # bounded
    assert snap.sample_window == 16
    assert np.isfinite(snap.p50_latency_us)
    assert np.isfinite(snap.mean_latency_us)   # exact running mean
    with pytest.raises(ValueError, match="window"):
        Telemetry(window=0)


def test_submit_vs_close_race_every_future_answered(rng):
    """Stress: clients submitting while the router closes — every
    future must settle (result, QueueFull, or the close error); none
    may hang."""
    q, r = _mk(rng, 1, 6)
    want = np.asarray(engine.sdtw(q, r))
    futs, errs, lock = [], [], threading.Lock()

    router = Router(RouterConfig(window_ms=1.0, max_queue=8,
                                 admission="reject"))

    def submitter():
        for _ in range(10):
            try:
                f = router.submit(queries=q, reference=r)
                with lock:
                    futs.append(f)
            except (QueueFull, RuntimeError) as e:
                with lock:
                    errs.append(e)

    threads = [threading.Thread(target=submitter) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.02)
    router.close(drain=False)
    for t in threads:
        t.join()
    answered = 0
    for f in futs:
        try:
            got = f.result(timeout=30.0)       # never hangs
            np.testing.assert_array_equal(np.asarray(got), want)
            answered += 1
        except (QueueFull, RuntimeError):
            pass
        except concurrent.futures.CancelledError:
            pass
    stats = router.stats()
    assert answered == stats.completed
    assert stats.completed + stats.unserved_on_close \
        + stats.shed + len(errs) >= len(futs) + len(errs)


# ---------------------------------------------------------------------------
# priorities, quotas, aging, shedding
# ---------------------------------------------------------------------------

def test_priority_drain_order_strict():
    q = AdmissionQueue(8, aging_s=None)
    q.put("lo", priority=0)
    q.put("hi", priority=5)
    q.put("mid", priority=2)
    q.put("hi2", priority=5)
    assert q.drain() == ["hi", "hi2", "mid", "lo"]   # desc, FIFO ties


def test_priority_aging_admits_starved_tenants():
    """With aging, a parked low-priority request eventually outranks
    fresh high-priority traffic (starvation freedom)."""
    q = AdmissionQueue(8, aging_s=0.01)
    q.put("starved-lo", priority=0)
    time.sleep(0.06)                     # ages >= 5 effective classes
    q.put("fresh-hi", priority=3)
    assert q.drain() == ["starved-lo", "fresh-hi"]

    q2 = AdmissionQueue(8, aging_s=None)  # aging off: strict priority
    q2.put("lo", priority=0)
    time.sleep(0.02)
    q2.put("hi", priority=3)
    assert q2.drain() == ["hi", "lo"]


def test_tenant_quota_rejects_overrun(rng):
    q, r = _mk(rng, 1, 6)
    router = Router(RouterConfig(auto_dispatch=False, tenant_quota=2))
    router.submit(queries=q, reference=r, tenant="greedy")
    router.submit(queries=q, reference=r, tenant="greedy")
    with pytest.raises(QueueFull, match="quota"):
        router.submit(queries=q, reference=r, tenant="greedy")
    router.submit(queries=q, reference=r, tenant="other")  # unaffected
    assert router.stats().rejected == 1
    router.drain()
    assert router.stats().completed == 3
    router.close()


def test_reject_shed_lowest_priority_first(rng):
    """Under 'reject', a higher-priority arrival sheds the newest
    lowest-priority pending request; its future fails with QueueFull."""
    q, r = _mk(rng, 1, 6)
    router = Router(RouterConfig(max_queue=2, admission="reject",
                                 aging_s=None, auto_dispatch=False))
    f_old = router.submit(queries=q, reference=r, priority=0)
    f_new = router.submit(queries=q, reference=r, priority=0)
    f_hi = router.submit(queries=q, reference=r, priority=5)  # sheds f_new
    with pytest.raises(QueueFull, match="shed"):
        f_new.result(timeout=1.0)
    # equal priority still rejects the arrival, never sheds
    with pytest.raises(QueueFull, match="full"):
        router.submit(queries=q, reference=r, priority=0)
    router.drain()
    want = np.asarray(engine.sdtw(q, r))
    np.testing.assert_array_equal(np.asarray(f_old.result(timeout=0)), want)
    np.testing.assert_array_equal(np.asarray(f_hi.result(timeout=0)), want)
    stats = router.stats()
    assert stats.shed == 1 and stats.rejected == 1
    assert stats.completed == 2
    router.close()


def test_reject_storm_under_priority_shed_accounting(rng):
    """Storm of mixed-priority submissions against a tiny reject queue
    with a concurrent drainer: every request is accounted for exactly
    once (completed / rejected / shed), and every success is bitwise."""
    q, r = _mk(rng, 1, 6)
    want = np.asarray(engine.sdtw(q, r))
    router = Router(RouterConfig(max_queue=4, admission="reject",
                                 aging_s=None, auto_dispatch=False))
    futs, sync_rejects, lock = [], [0], threading.Lock()
    stop = threading.Event()

    def drainer():
        while not stop.is_set():
            router.drain()
            time.sleep(0.002)
        router.drain()

    def submitter(prio):
        for _ in range(12):
            try:
                f = router.submit(queries=q, reference=r, priority=prio)
                with lock:
                    futs.append(f)
            except QueueFull:
                with lock:
                    sync_rejects[0] += 1

    d = threading.Thread(target=drainer)
    d.start()
    threads = [threading.Thread(target=submitter, args=(p,))
               for p in (0, 1, 2, 0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    d.join()
    completed = shed = 0
    for f in futs:
        try:
            np.testing.assert_array_equal(np.asarray(f.result(timeout=30.0)),
                                          want)
            completed += 1
        except QueueFull:
            shed += 1
    stats = router.stats()
    offered = 4 * 12
    assert completed + shed + sync_rejects[0] == offered
    assert stats.completed == completed
    assert stats.shed == shed
    assert stats.rejected == sync_rejects[0]
    router.close()


# ---------------------------------------------------------------------------
# in-window dedup
# ---------------------------------------------------------------------------

def test_dedup_identical_requests_share_call_and_result(rng):
    """Identical concurrent requests (equal bytes, different array
    objects) share ONE engine call and the SAME result object; a
    different request in the same window still coalesces normally."""
    r = rng.integers(-40, 40, 300).astype(np.int32)
    q = rng.integers(-40, 40, (2, 12)).astype(np.int32)
    other = rng.integers(-40, 40, (3, 12)).astype(np.int32)
    router = Router(RouterConfig(auto_dispatch=False))
    f1 = router.submit(queries=q, reference=r, ref_key="feed")
    f2 = router.submit(queries=q.copy(), reference=r, ref_key="feed")
    f3 = router.submit(queries=other, reference=r, ref_key="feed")
    router.drain()
    stats = router.stats()
    assert stats.dispatches == 1                # one merged call for all
    assert stats.deduped == 1
    assert stats.completed == 3
    g1, g2 = f1.result(timeout=0), f2.result(timeout=0)
    assert g1 is g2                             # bitwise-shared result
    np.testing.assert_array_equal(np.asarray(g1),
                                  np.asarray(engine.sdtw(q, r)))
    np.testing.assert_array_equal(np.asarray(f3.result(timeout=0)),
                                  np.asarray(engine.sdtw(other, r)))
    router.close()


def test_dedup_respects_content_and_shape(rng):
    """Same length but different bytes — or same bytes via a 1-D vs 2-D
    shape — must NOT dedup."""
    r = rng.integers(-40, 40, 200).astype(np.int32)
    q1 = rng.integers(-40, 40, (1, 8)).astype(np.int32)
    q2 = q1 + 1
    router = Router(RouterConfig(auto_dispatch=False))
    fa = router.submit(queries=q1, reference=r, ref_key="k")
    fb = router.submit(queries=q2, reference=r, ref_key="k")
    fc = router.submit(queries=q1[0], reference=r, ref_key="k")  # 1-D
    router.drain()
    assert router.stats().deduped == 0
    np.testing.assert_array_equal(np.asarray(fa.result(timeout=0)),
                                  np.asarray(engine.sdtw(q1, r)))
    np.testing.assert_array_equal(np.asarray(fb.result(timeout=0)),
                                  np.asarray(engine.sdtw(q2, r)))
    got_c = fc.result(timeout=0)
    assert np.asarray(got_c).shape == ()        # scalar unwrap preserved
    np.testing.assert_array_equal(np.asarray(got_c),
                                  np.asarray(engine.sdtw(q1[0], r)))
    router.close()


def test_dedup_can_be_disabled(rng):
    q, r = _mk(rng, 2, 8)
    router = Router(RouterConfig(auto_dispatch=False, dedup=False))
    f1 = router.submit(queries=q, reference=r)
    f2 = router.submit(queries=q.copy(), reference=r)
    router.drain()
    assert router.stats().deduped == 0
    assert f1.result(timeout=0) is not f2.result(timeout=0)
    np.testing.assert_array_equal(np.asarray(f1.result(timeout=0)),
                                  np.asarray(f2.result(timeout=0)))
    router.close()


# ---------------------------------------------------------------------------
# device pool
# ---------------------------------------------------------------------------

def test_device_pool_bitwise_equal_to_single_device_drain(rng):
    """The same request mix through a multi-worker device pool equals
    the single-device drain bitwise (and offline, transitively)."""
    import jax
    dev = jax.local_devices()[0]
    r = rng.integers(-40, 40, 300).astype(np.int32)
    clients = [rng.integers(-40, 40, (nq, 10 + nq)).astype(np.int32)
               for nq in (1, 2, 3, 4, 2)]

    def serve_all(devices):
        router = Router(RouterConfig(auto_dispatch=False, devices=devices))
        futs = [router.submit(queries=q, reference=r, metric=m)
                for q in clients for m in ("abs_diff", "square_diff")]
        router.drain()
        out = [np.asarray(f.result(timeout=0)) for f in futs]
        router.close()
        return out

    single = serve_all(None)
    pooled = serve_all([dev, dev, dev])     # 3 workers, shared device
    alldev = serve_all("all")
    for s, p, a in zip(single, pooled, alldev):
        np.testing.assert_array_equal(s, p)
        np.testing.assert_array_equal(s, a)


def test_device_pool_resolution_and_lifecycle():
    import jax
    with DevicePool(None) as pool:
        assert pool.size == 1 and pool.devices == [None]
    n = len(jax.local_devices())
    with DevicePool("all") as pool:
        assert pool.size == n
    with DevicePool(1) as pool:
        assert pool.size == 1
    with pytest.raises(ValueError, match="local device"):
        DevicePool(n + 1)
    with pytest.raises(ValueError, match="at least one"):
        DevicePool([])
    pool = DevicePool(None)
    pool.close()
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit([], None)


def test_device_pool_affinity_policy():
    """Executable-affinity routing: reuse a warm device when one is
    idle, grow onto a cold idle device only under same-shape pressure,
    and queue on warm rather than compile when everything is busy."""
    from repro.serve.pool import pick_device

    # Never-seen shape: globally least-loaded, lowest index on ties.
    assert pick_device([0, 0, 0], ()) == 0
    assert pick_device([2, 1, 2], ()) == 1
    # A warm device is idle: stay on it even though device 0 is idle too
    # (free cache reuse beats spreading).
    assert pick_device([0, 0, 0], {1}) == 1
    assert pick_device([1, 0, 1], {1, 2}) == 1
    # Warm merely busy (below GROW_LOAD): still queue on it — one group
    # in flight is every burst's steady state, not a backlog.
    assert pick_device([1, 0, 0], {0}) == 0
    # A genuinely backlogged warm set + a cold idle device: pay one
    # compile to grow the warm set (lowest cold idle index).
    assert pick_device([2, 0, 0], {0}) == 1
    assert pick_device([0, 2, 2], {1, 2}) == 0
    # Everything busy: queue on the least-loaded warm device — waiting
    # milliseconds beats compiling seconds on a cold one.
    assert pick_device([3, 4, 3], {1, 2}) == 2
    assert pick_device([9, 2, 2], {1}) == 1
    # A cold landing already in flight gates further growth: the same
    # pressure that would spread the shape must queue on warm instead
    # (one compile at a time per shape — no compile avalanche).
    assert pick_device([2, 0, 0], {0}, growing=True) == 0
    assert pick_device([0, 2, 2], {1, 2}, growing=True) == 1


def test_router_warmup_primes_every_device(rng):
    """``warmup`` compiles the request's bucket on every pool device
    and marks them all warm, so serving never routes that shape to a
    cold device."""
    from repro.core.request import SdtwRequest
    from repro.serve import batcher
    from repro.serve import pool as pool_mod

    pool_mod.clear_affinity_cache()
    r = rng.integers(-40, 40, 256).astype(np.int32)
    qs = [rng.integers(-40, 40, 16).astype(np.int32) for _ in range(4)]
    with Router(devices="all", auto_dispatch=False) as router:
        assert router.warmup(queries=qs, reference=r) == router._pool.size
        req = SdtwRequest.from_kwargs(queries=qs, reference=r)
        shape = batcher.group_shape(
            [batcher.Pending(request=req, future=None, trace=None)])
        assert set(router._pool.devices) <= pool_mod._warm_devices[shape]
        fut = router.submit(queries=qs, reference=r)
        router.drain()
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=60)),
                                      np.asarray(engine.sdtw(qs, r)))
    pool_mod.clear_affinity_cache()


# ---------------------------------------------------------------------------
# adaptive window
# ---------------------------------------------------------------------------

def test_adaptive_window_closes_early_when_bucket_fills(rng):
    """A filled pow-2 bucket must close the window immediately — a
    client never waits out a long base window once the batch is full."""
    r = rng.integers(-40, 40, 200).astype(np.int32)
    q = rng.integers(-40, 40, (4, 8)).astype(np.int32)   # weight 4
    expect = engine.sdtw(q, r)          # warm the jit cache: the timer
    with Router(window_ms=2000.0, window_full_queries=4) as router:
        t0 = time.monotonic()           # must see the window, not XLA
        got = router.sdtw(q, r)                # blocks until served
        elapsed = time.monotonic() - t0
        stats = router.stats()
    assert elapsed < 1.5, f"window did not close early ({elapsed:.2f}s)"
    assert stats.window_early_closes >= 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_queue_wait_weight_primitive():
    q = AdmissionQueue(8)
    q.put("a", weight=3)
    assert q.wait_weight(3, time.monotonic() + 5.0)      # already full
    assert not q.wait_weight(4, time.monotonic() + 0.02)  # expires
    assert q.pending_weight() == 3

    def late_put():
        time.sleep(0.02)
        q.put("b", weight=5)

    t = threading.Thread(target=late_put)
    t.start()
    assert q.wait_weight(8, time.monotonic() + 5.0)      # woken by put
    t.join()


def test_router_open_stream_and_stats(rng):
    ref = rng.integers(-40, 40, 256).astype(np.int32)
    q = rng.integers(-40, 40, (2, 8)).astype(np.int32)
    with Router(RouterConfig(auto_dispatch=False)) as router:
        router.open_stream("sensor", "t0", queries=q, chunk=32, top_k=2)
        assert router.feed("sensor", ref) == 1
        res = router.sessions.finalize("sensor")["t0"]
        d, _ = engine.sdtw(q, ref, top_k=2, chunk=32)
        np.testing.assert_array_equal(np.asarray(res.distances),
                                      np.asarray(d))
        snap = router.stats()
        assert snap.completed == snap.dispatches == 0


def test_serve_driver_exits_nonzero_on_failed_calls(monkeypatch):
    """``python -m repro.serve`` counts a client call that raised anything
    but ``QueueFull`` as failed, and then exits 1 instead of 0."""
    from repro.serve import __main__ as driver

    class Broken:
        def sdtw(self, *args, **kwargs):
            raise ValueError("engine blew up")

    assert driver.run_load(Broken(), clients=2, requests=3, op="sdtw",
                           top_k=None, nq=1, qlen=8, reflen=32) == (0, 0, 6)
    monkeypatch.setattr(driver, "enable_compile_cache", lambda: None)
    for outcome, code in (((3, 0, 1), 1), ((4, 0, 0), 0)):
        monkeypatch.setattr(driver, "run_load",
                            lambda router, outcome=outcome, **kw: outcome)
        assert driver.main(["--clients", "2", "--requests", "2"]) == code
