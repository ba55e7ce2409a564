"""Offline batch traffic: back-to-back ``repro.core.sdtw`` calls.

Mix parameters: ``spans`` (ask for match spans), ``batches`` (query
batches generated at set-up and cycled through the window) and
``planted`` (queries per batch cut from the reference, whose answer is
known). The configuration gives the reference length ``ref_size``, the
query length ``query_size`` and the queries per call ``num_queries``.

Calls run back to back on the device: the next call is handed to the
program before the answers of the one ahead of it are fetched, so that
``IN_FLIGHT`` calls are queued and a short host stall leaves the device
busy, as a batch job that keeps its accelerator fed runs. Every call ends
when its answers are on the host. A call starts only while the window is
less than ``seconds`` old; the window closes at the end of the last call,
and ``cells_per_s`` is the nominal cells of every call over the whole
window. Host spans (``bench.engine`` from entry into ``sdtw`` to its
return of not-yet-awaited arrays, ``bench.fetch`` from the start of the
wait for a call's answers until they are on the host) mark what the host
was doing in the trace.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import data, reference, work

#: Calls handed to the program and not yet fetched, at most.
IN_FLIGHT = 2


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        import jax
        from repro.core import sdtw
        self._sdtw = sdtw
        self._annotate = jax.profiler.TraceAnnotation
        self.spans = bool(mix["spans"])
        self.n = int(config["query_size"])
        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)
        self.ref = data.synthetic_timeseries(rng, int(config["ref_size"]))
        self.batches = [data.make_batch(rng, self.ref, self.n,
                                        int(config["num_queries"]),
                                        int(mix["planted"]))
                        for _ in range(int(mix["batches"]))]
        t1 = time.perf_counter()
        for x in self._call(self.batches[0][0]):   # compiles, runs once,
            np.asarray(x)                           # and is done
        self.setup_parts = {"data_s": t1 - t0,
                            "warm_call_s": time.perf_counter() - t1}
        self.calls = []

    def _call(self, q):
        out = self._sdtw(q, self.ref, return_spans=self.spans)
        return out if self.spans else (out,)

    def window(self, seconds: float) -> dict:
        calls, inflight = [], collections.deque()
        t0 = time.perf_counter()
        while True:
            if (len(inflight) < IN_FLIGHT
                    and time.perf_counter() - t0 < seconds):
                b = (len(calls) + len(inflight)) % len(self.batches)
                ts = time.perf_counter()
                with self._annotate("bench.engine"):
                    out = self._call(self.batches[b][0])
                inflight.append((b, ts, time.perf_counter(), out))
                continue
            if not inflight:
                break
            b, ts, tr, out = inflight.popleft()
            with self._annotate("bench.fetch"):
                answers = tuple(np.asarray(x) for x in out)
            calls.append(dict(work.sdtw_call(self.batches[b][0].shape[0],
                                             self.n, self.ref.shape[0],
                                             self.spans),
                              batch=b, t_start=ts, t_return=tr,
                              t_end=time.perf_counter(), answers=answers))
        self.calls = calls
        window_s = calls[-1]["t_end"] - t0
        total = sum(c["cells"] for c in calls)
        return {"attempted": len(calls), "failed": 0,
                "metrics": {"cells_per_s": total / window_s},
                "calls": [{k: v for k, v in c.items() if k != "answers"}
                          for c in calls],
                "notes": {"calls": len(calls), "window_s": window_s,
                          "cells": total}}

    def close(self):
        pass

    def answer_with(self, fn):
        """Put ``fn(queries, reference, spans)`` in the program's place:
        every call's answers become ``fn``'s for the same batch."""
        got = {}
        for c in self.calls:
            if c["batch"] not in got:
                got[c["batch"]] = fn(self.batches[c["batch"]][0], self.ref,
                                     self.spans)
            c["answers"] = got[c["batch"]]

    def check(self) -> dict:
        """Every answer of every call in the window against the plain
        reference, and the planted queries against their known spans."""
        dist = span = planted = 0
        want = {}
        for c in self.calls:
            b = c["batch"]
            q, offs = self.batches[b]
            if b not in want:
                want[b] = reference.sdtw(q, self.ref, spans=self.spans)
            got = c["answers"]
            dist += int(np.sum(got[0] != want[b][0]))
            if self.spans:
                _, ws, we = want[b]
                span += int(np.sum((got[1] != ws) | (got[2] != we)))
                planted += int(np.sum((got[0][:len(offs)] != 0)
                                      | (got[1][:len(offs)] != offs)
                                      | (got[2][:len(offs)]
                                         != offs + self.n - 1)))
            else:
                planted += int(np.sum(got[0][:len(offs)] != 0))
        checks = {"dist_wrong": {"value": dist, "limit": 0},
                  "planted_wrong": {"value": planted, "limit": 0}}
        if self.spans:
            checks["span_wrong"] = {"value": span, "limit": 0}
        return checks
