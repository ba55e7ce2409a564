"""Open-loop served traffic through ``repro.serve.Router``.

Requests arrive on a fixed schedule whatever the server does, so a stall
makes later requests wait (an open loop). Each request asks for the
plain distances of its queries against the configuration's one
reference, which the service holds on the device. Mix parameters:

  rate_per_s           offered requests per second, fixed in the mix
  queries_per_request  queries of the configuration's length per request
  query_pool           length of the series the queries are cut from
  router               ``RouterConfig`` fields

A window of ``seconds`` holds ``round(rate_per_s * seconds)`` requests.
Every seed gets the same inter-arrival gaps (the quantiles of an
exponential distribution with mean ``1 / rate_per_s``, i.e. a Poisson
process's gaps), in an order drawn from the seed; the seed also draws
the reference and the queries. A request's latency runs from when it was
due to when its answer is ready, waited for with ``jax.block_until_ready``
on the arrays the Router delivers (they are copied to the host once the
window has closed, so that the harness's copies do not compete with the
Router's threads inside it); a request the Router refuses (``QueueFull``,
admission or shed) is a failure and has no latency.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench import data, reference

#: How long after the last due time the window waits for answers.
DRAIN_S = 60.0


def schedule(mix: dict, seconds: float, rng) -> np.ndarray:
    """Due times in seconds from the window's start, one per request."""
    rate = float(mix["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps)


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        import jax
        from repro.serve import Router, RouterConfig
        from repro.serve.pool import resolve_devices
        self._annotate = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        n, k = int(config["query_size"]), int(mix["queries_per_request"])
        self.ref_host = data.synthetic_timeseries(rng, int(config["ref_size"]))
        pool = data.synthetic_timeseries(rng, int(mix["query_pool"]))
        self.due = schedule(mix, seconds, rng)
        st = rng.integers(0, pool.shape[0] - n, (self.due.size, k))
        self.queries = pool[st[..., None] + np.arange(n)]
        t1 = time.perf_counter()
        self.ref = jax.device_put(self.ref_host)
        cfg = RouterConfig(**mix["router"])
        self.router = Router(cfg)
        self.n_devices = len(resolve_devices(cfg.devices))
        # Each request dispatches alone (the mix caps a coalescing window
        # at one request), as one (k, N) array: warm that shape.
        self.router.warmup(queries=self.queries[0], reference=self.ref)
        # The warm call returns before the device has run it; one more
        # operation, waited for, runs after it.
        jax.block_until_ready(self.ref + 0)
        self.setup_parts = {"data_s": t1 - t0,
                            "warm_s": time.perf_counter() - t1}
        self.answers = {}
        self.lost = 0

    def window(self, seconds: float) -> dict:
        import jax
        from repro.serve import QueueFull
        done: queue.SimpleQueue = queue.SimpleQueue()
        answers, t_done, refused, shed, lost = {}, {}, [], [], []
        cond = threading.Condition()
        count = {"admitted": 0, "received": 0}

        def receive():
            while (item := done.get()) is not None:
                i, fut = item
                exc = fut.exception()
                if exc is None:
                    with self._annotate("bench.receive"):
                        res = jax.block_until_ready(fut.result())
                    t_done[i] = time.perf_counter()
                    answers[i] = res
                elif isinstance(exc, QueueFull):
                    shed.append(i)
                else:
                    lost.append(i)
                with cond:
                    count["received"] += 1
                    cond.notify_all()

        receiver = threading.Thread(target=receive, name="bench-receive",
                                    daemon=True)
        receiver.start()
        c0 = self.router.stats()
        late = []
        t0 = time.perf_counter()
        for i, due in enumerate(self.due):
            t_due = t0 + due
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late.append(time.perf_counter() - t_due)
            try:
                with self._annotate("bench.submit"):
                    fut = self.router.submit(queries=self.queries[i],
                                             reference=self.ref)
            except QueueFull:
                refused.append(i)
                continue
            with cond:
                count["admitted"] += 1
            fut.add_done_callback(lambda f, i=i: done.put((i, f)))
        t_close = time.perf_counter()
        with cond:
            cond.wait_for(lambda: count["received"] >= count["admitted"],
                          timeout=DRAIN_S)
            never = count["admitted"] - count["received"]
        done.put(None)
        receiver.join(timeout=DRAIN_S)
        c1 = self.router.stats()
        self.answers, self.lost = answers, len(lost) + never
        lat = np.asarray([t_done[i] - (t0 + self.due[i]) for i in answers])
        p50, p95 = (np.percentile(lat, [50, 95]) * 1e3 if lat.size
                    else (float("nan"),) * 2)
        completed = c1.completed - c0.completed
        counters = {
            "completed": completed,
            "dispatches": c1.dispatches - c0.dispatches,
            # the Router's own enqueue-to-delivery latency, summed over
            # the window's requests (its running mean is exact)
            "router_ms_sum": (c1.mean_latency_us * c1.completed
                              - (c0.mean_latency_us * c0.completed
                                 if c0.completed else 0.0)) / 1e3,
            # its enqueue-to-dispatch wait: the median of its ring, which
            # holds only the window's requests (warm-up bypasses it)
            "queue_p50_ms": c1.p50_queue_us / 1e3}
        late = np.asarray(late)
        worst = int(np.argmax(late))
        return {"attempted": self.due.size,
                "failed": len(refused) + len(shed),
                "metrics": {"served_p50_ms": p50, "served_p95_ms": p95},
                "counters": counters,
                "notes": {"requests": int(self.due.size),
                          "answered": len(answers), "refused": len(refused),
                          "shed": len(shed), "unanswered": self.lost,
                          "offered_s": float(self.due[-1]),
                          "drain_s": time.perf_counter() - t_close,
                          "generator_late_p95_ms": float(
                              np.percentile(late, 95) * 1e3),
                          "generator_late_max_ms": float(late[worst] * 1e3),
                          # wall-clock time of the worst lateness, to set
                          # beside other processes' clocks
                          "generator_late_max_unix_s": time.time() - (
                              time.perf_counter() - t0 - self.due[worst]),
                          **counters}}

    def close(self):
        self.router.close()
        self.ref = None

    def _to_host(self):
        self.answers = {i: np.asarray(a) for i, a in self.answers.items()}

    def _answered(self):
        idx = sorted(self.answers)
        q = self.queries[idx].reshape(-1, self.queries.shape[-1])
        return idx, q

    def answer_with(self, fn):
        """Put ``fn(queries, reference, spans)`` in the program's place:
        every answered request's answer becomes ``fn``'s."""
        idx, q = self._answered()
        if not idx:
            return
        got = fn(q, self.ref_host, False)[0].reshape(len(idx), -1)
        self.answers = dict(zip(idx, got))

    def check(self) -> dict:
        """Every answered request's distances against the plain reference.
        An admitted request that was never answered, or failed other than
        by refusal, counts in ``unanswered``."""
        self._to_host()
        idx, q = self._answered()
        dist = 0
        if idx:
            want, _ = reference.sdtw(q, self.ref_host, spans=False,
                                     block=2048)
            got = np.stack([self.answers[i] for i in idx]).reshape(-1)
            dist = int(np.sum(got != want))
        return {"dist_wrong": {"value": dist, "limit": 0},
                "unanswered": {"value": self.lost, "limit": 0}}
