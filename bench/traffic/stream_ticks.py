"""Open-loop ticks of many aligned streams through one monitoring session.

The configuration gives the deployment: ``streams`` monitored on the
chip, a library of ``templates`` of ``query_size`` samples shared by
every stream, the sensors' ``sample_rate_hz`` and the ``alert_threshold``.
Mix parameters:

  ticks_per_s     ticks per second: each delivers ``sample_rate_hz /
                  ticks_per_s`` samples of every stream, all streams
                  aligned
  plant_every_s   about one exact template copy per stream per this many
                  seconds, at seeded offsets, of seeded templates

The templates are anomaly templates: windows of an independent series
holding one burst of the signal model's anomaly, the stand-in for an
abnormal beat. The program opens one session over all streams
(``repro.core.stream(..., streams=S)``, tile = one tick) and is fed one
tick at a time. A window of
``seconds`` holds ``round(ticks_per_s * seconds)`` ticks, due on a fixed
schedule whatever the program does (an open loop). A tick's latency runs
from when it was due until ``feed`` returns, which is when every stream's
alerts for it are on the host; a tick that was never fed by the end of
the window's time limit is unanswered. The seed draws the streams (one
``bench/data.py`` series each), the templates (cut from an independent
series, each with its burst) and the planted copies; every seed gives
the same shapes.

``check()`` compares, for every (stream, template), the window's alerts
tick by tick (hits, least cost, leftmost end column) and the distance at
the window's end with ``bench/reference_stream.py``'s last rows over the
samples fed, and holds every planted copy to an alert of cost 0 in the
tick where the copy ends.
"""
from __future__ import annotations

import time

import numpy as np

from bench import data, reference_stream, work

#: How long past the last due time the window keeps feeding ticks.
DRAIN_S = 60.0


def templates(rng, count: int, n: int) -> np.ndarray:
    """``count`` anomaly templates of ``n`` samples: windows of an
    independent series of the signal model, each holding one burst of the
    model's anomaly (N(0, 800) noise over ``min(64, n // 2)`` samples) at
    a seeded place."""
    other = data.synthetic_timeseries(rng, max(4 * n, 64 * count))
    st = rng.integers(0, other.shape[0] - n, count)
    out = other[st[:, None] + np.arange(n)].astype(np.float64)
    width = min(64, n // 2)
    for row, a in zip(out, rng.integers(0, n - width + 1, count)):
        row[a:a + width] += rng.normal(0, 800, width)
    return out.astype(np.int32)


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        import jax
        from repro.core import stream
        self._annotate = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        self.n = n = int(config["query_size"])
        nq, ns = int(config["templates"]), int(config["streams"])
        rate = float(mix["ticks_per_s"])
        self.tick = int(round(config["sample_rate_hz"] / rate))
        self.thr = int(config["alert_threshold"])
        self.count = max(1, int(round(rate * seconds)))
        self.due = np.arange(self.count) / rate
        self.templates = templates(rng, nq, n)

        def open_session():
            return stream(self.templates, streams=ns, chunk=self.tick,
                          alert_threshold=self.thr)

        # Opened before the streams are drawn: a program without
        # many-stream sessions fails here at once.
        self.session = open_session()
        length = self.count * self.tick
        self.streams = data.synthetic_timeseries(rng, ns * length).reshape(
            ns, length)
        self.planted = self._plant(rng, float(mix["plant_every_s"]) * rate
                                   * self.tick)
        t1 = time.perf_counter()
        # Warm up on a session of the same shapes; the measured one is
        # left untouched.
        warm = open_session()
        for i in range(2):
            warm.feed(self.streams[:, i * self.tick:(i + 1) * self.tick])
        warm.results()
        del warm
        self.setup_parts = {"data_s": t1 - t0,
                            "warm_s": time.perf_counter() - t1}
        self.answered = 0
        self.events = []
        self.final = None

    def _plant(self, rng, every: float) -> np.ndarray:
        """Copy templates into the streams: about ``length / every`` per
        stream, apart by more than a template, fully inside the window.
        Returns ``(stream, template, end column)`` rows."""
        ns, length = self.streams.shape
        per = max(1, int(round(length / every)))
        slot = length // per
        out = []
        for s in range(ns):
            for k in range(per):
                o = k * slot + int(rng.integers(0, slot - self.n))
                q = int(rng.integers(0, self.templates.shape[0]))
                self.streams[s, o:o + self.n] = self.templates[q]
                out.append((s, q, o + self.n - 1))
        return np.asarray(out, np.int64)

    def window(self, seconds: float) -> dict:
        s, tick = self.session, self.tick
        c0 = s.counters()
        calls, late = [], []
        t0 = time.perf_counter()
        for i, due in enumerate(self.due):
            t_due = t0 + due
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            t_start = time.perf_counter()
            if t_start - t0 > self.due[-1] + DRAIN_S:
                break
            late.append(t_start - t_due)
            with self._annotate("bench.tick"):
                s.feed(self.streams[:, i * tick:(i + 1) * tick])
            calls.append(dict(self._work(), t_due=t_due, t_start=t_start,
                              t_end=time.perf_counter()))
        self.answered = len(calls)
        c1 = s.counters()
        self.events = list(s.alerts)
        counters = {k: c1[k] - c0[k] for k in c1}
        counters["ticks"] = self.answered
        lat = np.asarray([c["t_end"] - c["t_due"] for c in calls])
        p50 = (float(np.percentile(lat, 50) * 1e3) if lat.size
               else float("nan"))
        return {"attempted": self.count,
                "failed": self.count - self.answered,
                "metrics": {"served_p50_ms": p50},
                "calls": calls, "counters": counters,
                "notes": {"ticks": self.count, "answered": self.answered,
                          "alerts": len(self.events),
                          "planted": len(self.planted),
                          "latency_max_ms": float(lat.max() * 1e3)
                          if lat.size else float("nan"),
                          "generator_late_max_ms": float(
                              max(late) * 1e3) if late else float("nan"),
                          **counters}}

    def _work(self) -> dict:
        """One tick: every stream's templates against its tick's samples,
        in nominal cells of the true tick (not of any padded tile)."""
        ns, nq = self.streams.shape[0], self.templates.shape[0]
        one = work.sdtw_call(nq, self.n, self.tick, spans=False)
        return {k: ns * v for k, v in one.items()}

    def close(self):
        self.final = np.asarray(self.session.results().distances)
        self.session = None

    def answer_with(self, fn):
        """Put ``fn(queries, reference, spans)`` in the program's place for
        the distances: each stream's become ``fn``'s for its samples."""
        fed = self.streams[:, :self.answered * self.tick]
        self.final = np.stack([fn(self.templates, r, False)[0]
                               for r in fed])

    def check(self) -> dict:
        """The window's alerts and final distances against the plain
        reference, and the planted copies against their known alerts."""
        import jax
        import jax.numpy as jnp
        ns, nq = self.streams.shape[0], self.templates.shape[0]
        ticks, tick = self.answered, self.tick
        unanswered = {"value": self.count - ticks, "limit": 0}
        if not ticks:
            return {"dist_wrong": {"value": ns * nq, "limit": 0},
                    "alert_wrong": {"value": 0, "limit": 0},
                    "planted_missed": {"value": len(self.planted),
                                       "limit": 0},
                    "unanswered": unanswered}
        rows = reference_stream.last_rows(self.templates,
                                          self.streams[:, :ticks * tick])
        per = rows.reshape(ns, nq, ticks, tick)
        hit = per <= self.thr
        least = jnp.min(jnp.where(hit, per, jnp.iinfo(per.dtype).max),
                        axis=-1)
        at = hit & (per == least[..., None])
        want = jax.device_get((
            jnp.sum(hit, axis=-1, dtype=jnp.int32), least,
            jnp.argmax(at, axis=-1).astype(jnp.int32),
            jnp.min(rows, axis=-1)))
        del rows, per, hit, at
        w_hits, w_least, w_col, w_dist = (np.asarray(x) for x in want)
        # the program's alerts in the same layout: (stream, template, tick)
        g_hits = np.zeros_like(w_hits)
        g_least = np.zeros_like(w_least)
        g_col = np.zeros_like(w_col)
        for e in self.events:
            t = e.tile_start // tick
            g_hits[e.stream, e.query, t] += e.hits
            g_least[e.stream, e.query, t] = e.distance
            g_col[e.stream, e.query, t] = e.end - e.tile_start
        fired = w_hits > 0
        wrong = ((g_hits != w_hits)
                 | (fired & ((g_least != w_least) | (g_col != w_col))))
        missed = 0
        for s, q, end in self.planted:
            t = end // tick
            if t >= ticks or g_hits[s, q, t] == 0 or g_least[s, q, t] != 0:
                missed += 1
        return {"dist_wrong": {"value": int(np.sum(self.final != w_dist)),
                               "limit": 0},
                "alert_wrong": {"value": int(np.sum(wrong)), "limit": 0},
                "planted_missed": {"value": missed, "limit": 0},
                "unanswered": unanswered}
