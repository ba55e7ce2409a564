#!/usr/bin/env python3
"""Smoke run of the sDTW engine on a TPU at the paper's Table V widths.

    python3 chip_smoke.py [--seed S]        # one chip, phases a-e
    python3 chip_smoke.py --chips 4 [...]   # four chips, phases f-g only

One process, no children. The phases drive the entry points a user calls:

  a offline  ``sdtw(return_spans=True, explain=True)``, ECG shape: the full
             1,800,000-sample int32 reference, N = 512, 64 queries
  b batch    ``sdtw(return_spans=True)`` on the Human shape: M = 7,997,
             N = 120, the full batch of 131,072 queries
  c search   ``search_topk(k=4)`` on the ECG reference
  d stream   ``stream(top_k=4, return_spans=True)`` fed the ECG reference
             in uneven pieces
  e served   a ``Router`` answering 32 requests from 4 client threads
  f sharded  ``sdtw(mesh_shape=(1, 4))`` and ``(4, 1)`` on the ECG
             reference, each against the same call on one device
  g served4  a 4-device ``Router`` against the one-device offline call

Inputs come from ``--seed``. Some queries are cut from the reference at
known offsets, so their answer must be distance 0 at the known span. The
rest are checked bitwise: against the numpy oracle (``tests/oracle.py``)
on windows small enough for it, (d) against (a), (c) against (a) under its
span cap, and served answers against each client's offline call. Phases
a-d must run the Pallas kernel. Each phase prints its dispatch decision,
compile seconds (the backend compile time JAX's monitoring events
report, so a persistent-cache hit shows as a drop) and wall seconds; a
cells/s figure printed here is a smoke reading, not a benchmark. The
compile cache is the one ``repro.compile_cache`` sets up. The last line
of standard output is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every phase passed; any failure, and any backend but
the TPU, exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Phase sizes; the defaults are the paper's Table V widths."""
    ecg_m: int = 1_800_000
    ecg_n: int = 512
    ecg_nq: int = 64
    human_m: int = 7_997
    human_n: int = 120
    human_nq: int = 131_072
    planted: int = 8            # queries cut from the reference, per batch
    oracle_queries: int = 3     # non-planted queries re-scored by the oracle
    oracle_window: int = 768    # reference window of the small-window check
    search_k: int = 4
    stream_piece: int = 20_000  # mean fed piece (uneven, seeded)
    served_clients: int = 4
    served_requests: int = 8    # per client
    sharded_nq: int = 16        # the sharded step is the XLA rowscan
    served4_clients: int = 8
    served4_requests: int = 4


#: Queries per served request.
SERVED_NQ = 2


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Timing: compile seconds from JAX's monitoring events, wall from the clock
# ---------------------------------------------------------------------------

#: XLA/Mosaic compile time of each executable: the work the persistent
#: compilation cache saves (on a hit the event times the cache read).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums backend compile seconds and persistent-cache hits, from any
    thread."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def read(self):
        with self._lock:
            return self.seconds, self.cache_hits


class Phase:
    """One phase: prints its decision, compile and wall seconds, checks."""

    def __init__(self, tag: str, clock: CompileClock):
        self.tag = tag
        self.clock = clock
        self.cells = 0

    def say(self, msg: str):
        print(f"[{self.tag}] {msg}", flush=True)

    def decision(self, impl: str, source: str, detail: str = ""):
        self.say(f"dispatch impl={impl} source={source}"
                 + (f" {detail}" if detail else ""))

    def __enter__(self):
        self.say("start")
        self.c0, self.h0 = self.clock.read()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        c1, h1 = self.clock.read()
        line = (f"compile_s={c1 - self.c0:.3f} cache_hits={h1 - self.h0} "
                f"wall_s={wall:.3f}")
        if self.cells:
            line += (f" cells={self.cells:.4g} smoke_cells_per_s="
                     f"{self.cells / wall:.4g} (smoke reading, not a "
                     f"benchmark)")
        self.say(line)
        return False


# ---------------------------------------------------------------------------
# Data, from the seed
# ---------------------------------------------------------------------------

def plant_offsets(rng, ref, n, count):
    """``count`` distinct offsets whose cut ``ref[o:o+n]`` has a unique
    zero-cost span: the sample before it differs from its first (else the
    leftmost-start rule would widen the span by one)."""
    out = []
    while len(out) < count:
        o = int(rng.integers(1, ref.shape[0] - n))
        if ref[o - 1] != ref[o] and all(abs(o - p) > n for p in out):
            out.append(o)
    return np.asarray(out, np.int64)


def make_batch(rng, ref, n, nq, planted):
    """(queries (nq, n) int32, planted offsets): the first ``planted`` rows
    are cut from ``ref``; the rest from an independent series of the same
    generator."""
    from repro.core import synthetic_timeseries
    offs = plant_offsets(rng, ref, n, planted)
    other = synthetic_timeseries(rng, max(4 * n, min(8 * nq, 1 << 20)))
    starts = rng.integers(0, other.shape[0] - n, nq - planted)
    q = np.empty((nq, n), np.int32)
    q[:planted] = ref[offs[:, None] + np.arange(n)]
    q[planted:] = other[starts[:, None] + np.arange(n)]
    return q, offs


def check_planted(ph, offs, n, d, s, e):
    for i, o in enumerate(offs):
        got = (int(d[i]), int(s[i]), int(e[i]))
        check(got == (0, o, o + n - 1),
              f"planted query {i}: got {got}, want (0, {o}, {o + n - 1})")
    ph.say(f"check planted: {len(offs)}/{len(offs)} at distance 0 on the "
           "known span")


def check_span_oracle(ph, ref, q, idxs, d, s, e):
    """Re-score reported spans with the oracle: the sDTW of a query
    against exactly its reported window ``ref[s:e+1]`` must come back as
    the same distance, starting at the window's first column and ending
    at its last."""
    from oracle import sdtw_span
    for i in idxs:
        si, ei = int(s[i]), int(e[i])
        want = sdtw_span(q[i], ref[si:ei + 1])
        got = (float(d[i]), 0, ei - si)
        check(got == want, f"query {i} span [{si}, {ei}]: engine {got}, "
                           f"oracle {want}")
    ph.say(f"check oracle on reported spans: {len(idxs)} queries equal")


def spread(nq, planted, count):
    """Indices of ``count`` non-planted queries spread over the batch."""
    if nq <= planted:
        return []
    return sorted({int(i) for i in np.linspace(planted, nq - 1, count)})


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_offline(clock, sz, ref, q, offs):
    from oracle import sdtw_span
    from repro.core import sdtw
    with Phase("a offline", clock) as ph:
        (d, s, e), dec = sdtw(q, ref, return_spans=True, explain=True)
        d, s, e = np.asarray(d), np.asarray(s), np.asarray(e)
        ph.decision(dec.impl, dec.source, f"config={dec.config}")
        ph.cells = q.size * ref.shape[0]
    check(dec.impl == "pallas", f"offline ran {dec.impl}, not pallas")
    check_planted(ph, offs, sz.ecg_n, d, s, e)
    check_span_oracle(ph, ref, q,
                      [0, *spread(len(q), len(offs), sz.oracle_queries)],
                      d, s, e)
    # The kernel's full output on a window the oracle can run whole.
    w0 = int(offs[0]) - (sz.oracle_window - sz.ecg_n) // 2
    win = ref[w0:w0 + sz.oracle_window]
    pick = [0, *spread(len(q), len(offs), sz.oracle_queries)]
    wd, ws, we = (np.asarray(x) for x in
                  sdtw(q[pick], win, return_spans=True))
    for k, i in enumerate(pick):
        want = sdtw_span(q[i], win)
        got = (float(wd[k]), int(ws[k]), int(we[k]))
        check(got == want, f"window check, query {i}: engine {got}, "
                           f"oracle {want}")
    ph.say(f"check oracle on a {sz.oracle_window}-sample window: "
           f"{len(pick)} queries equal (distance, start, end)")
    return d, s, e


def phase_batch(clock, sz, rng):
    from oracle import sdtw_span
    from repro.core import sdtw, synthetic_timeseries
    ref = synthetic_timeseries(rng, sz.human_m)
    q, offs = make_batch(rng, ref, sz.human_n, sz.human_nq, sz.planted)
    with Phase("b batch", clock) as ph:
        if sz.human_nq != Sizes.human_nq:
            ph.say(f"batch cut: {sz.human_nq} of {Sizes.human_nq} queries")
        (d, s, e), dec = sdtw(q, ref, return_spans=True, explain=True)
        d, s, e = np.asarray(d), np.asarray(s), np.asarray(e)
        ph.decision(dec.impl, dec.source, f"config={dec.config}")
        ph.cells = q.size * ref.shape[0]
    check(dec.impl == "pallas", f"batch ran {dec.impl}, not pallas")
    check_planted(ph, offs, sz.human_n, d, s, e)
    idxs = spread(len(q), len(offs), sz.oracle_queries + 1)
    check_span_oracle(ph, ref, q, idxs, d, s, e)
    for i in idxs[:2]:          # the whole Human reference is oracle-sized
        want = sdtw_span(q[i], ref)
        got = (float(d[i]), int(s[i]), int(e[i]))
        check(got == want, f"full-reference check, query {i}: engine {got},"
                           f" oracle {want}")
    ph.say(f"check oracle on the whole reference: {len(idxs[:2])} queries "
           "equal")


def phase_search(clock, sz, ref, q, offs, offline):
    from oracle import dtw_ref
    from repro.search import search_topk
    k = sz.search_k
    with Phase("c search", clock) as ph:
        res = search_topk(q, ref, k=k)
        d = np.asarray(res.distances)
        p = np.asarray(res.positions)
        s = np.asarray(res.starts)
        ph.decision(res.engine_impl, "auto",
                    f"chunk={res.chunk} chunks_processed="
                    f"{res.chunks_processed}/{res.chunks_total}")
        # each processed chunk is scored with one halo chunk of context
        ph.cells = res.chunks_processed * q.size * 2 * res.chunk
    check(res.engine_impl == "pallas",
          f"search ran {res.engine_impl}, not pallas")
    check(d.shape == (len(q), k), f"search distances shape {d.shape}")
    check(bool(np.all(np.diff(d, axis=1) >= 0)), "top-k not best-first")
    check_planted(ph, offs, sz.ecg_n, d[:, 0], s[:, 0], p[:, 0])
    od, os_, oe = offline
    # Under the span cap (2N) the pruned top-1 distance is exact.
    capped = (oe - os_ + 1) <= 2 * sz.ecg_n
    check(bool(np.array_equal(d[capped, 0], od[capped])),
          "search top-1 differs from offline (a) under the span cap")
    ph.say(f"check top-1 == offline (a): {int(capped.sum())}/{len(q)} "
           "queries under the span cap, all equal")
    # Every reported (distance, start, end) is a real alignment cost.
    n_checked = 0
    for i in spread(len(q), len(offs), 2):
        for j in range(k):
            if p[i, j] < 0:
                continue
            want = dtw_ref(q[i], ref[s[i, j]:p[i, j] + 1])
            check(float(d[i, j]) == want,
                  f"search entry ({i}, {j}) [{s[i, j]}, {p[i, j]}]: "
                  f"engine {d[i, j]}, oracle DTW {want}")
            n_checked += 1
    ph.say(f"check oracle DTW of reported spans: {n_checked} entries equal")


def phase_stream(clock, sz, rng, ref, q, offline):
    from repro.core import stream
    pieces = []
    pos = 0
    while pos < ref.shape[0]:
        step = int(rng.integers(1, 2 * sz.stream_piece))
        pieces.append(ref[pos:pos + step])
        pos += step
    with Phase("d stream", clock) as ph:
        sess = stream(q, top_k=sz.search_k, return_spans=True)
        ph.decision(sess.impl, "auto", f"tile={sess.chunk} "
                    f"pieces={len(pieces)}")
        for piece in pieces:
            sess.feed(piece)
        res = sess.results()
        d = np.asarray(res.distances)
        s = np.asarray(res.starts)
        p = np.asarray(res.positions)
        ph.cells = q.size * ref.shape[0]
    check(sess.impl == "pallas", f"stream ran {sess.impl}, not pallas")
    check(res.samples == ref.shape[0], f"stream saw {res.samples} samples")
    od, os_, oe = offline
    for name, got, want in (("distance", d[:, 0], od), ("start", s[:, 0], os_),
                            ("end", p[:, 0], oe)):
        check(bool(np.array_equal(got, want)),
              f"stream top-1 {name} differs from offline (a)")
    ph.say(f"check top-1 (distance, start, end) == offline (a): {len(q)} "
           "queries equal")


def serve_traffic(router, rng, ref, sz_n, clients, per_client, tag):
    """Clients submit ``sdtw`` requests of ``SERVED_NQ`` queries (spans or
    distances, alternating) and compare every served answer with their
    own offline call. Returns (requests answered, per-device answer
    counts). A coalesced batch compiles once per distinct query count,
    so the per-request count is fixed to keep those compiles few."""
    from repro.core import sdtw, synthetic_timeseries
    other = synthetic_timeseries(rng, 1 << 16)
    plans = []
    for ci in range(clients):
        plan = []
        for r in range(per_client):
            st = rng.integers(0, other.shape[0] - sz_n, SERVED_NQ)
            q = other[st[:, None] + np.arange(sz_n)]
            plan.append((q, bool((ci + r) % 2)))
        plans.append(plan)
    errors, answered, on_device = [], [0], {}
    lock = threading.Lock()

    def client(ci):
        try:
            for q, spans in plans[ci]:
                got = router.sdtw(q, ref, return_spans=spans,
                                  tenant=f"client{ci}")
                want = sdtw(q, ref, return_spans=spans)
                got = got if spans else (got,)
                want = want if spans else (want,)
                for g, w in zip(got, want):
                    if not np.array_equal(np.asarray(g), np.asarray(w)):
                        raise SmokeFailure(f"{tag}: client {ci} served "
                                           "answer differs from offline")
                dev = str(next(iter(got[0].devices())))
                with lock:
                    answered[0] += 1
                    on_device[dev] = on_device.get(dev, 0) + 1
        except Exception as exc:                       # noqa: BLE001
            with lock:
                errors.append((ci, exc))

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    check(not any(t.is_alive() for t in threads), f"{tag}: a client hung")
    if errors:
        ci, exc = errors[0]
        raise SmokeFailure(f"{tag}: {len(errors)} client(s) failed; client "
                           f"{ci}: {exc!r}") from exc
    return answered[0], on_device


def phase_served(clock, sz, rng, ref):
    from repro.core.engine import choose_impl_explained
    from repro.serve import Router
    with Phase("e served", clock) as ph:
        impl, source, reason, _ = choose_impl_explained(
            SERVED_NQ, sz.human_n, ref.shape[0], tune="model")
        ph.decision(impl, source, f"({reason}; engine rule for the served "
                                  "buckets)")
        with Router() as router:
            n, _ = serve_traffic(router, rng, ref, sz.human_n,
                                 sz.served_clients, sz.served_requests,
                                 "served")
            stats = router.stats().as_dict()
    want = sz.served_clients * sz.served_requests
    check(n == want, f"served {n} of {want} requests")
    ph.say(f"check served == offline: {n}/{want} requests equal "
           f"(dispatches={stats.get('dispatches')})")


def run_one_chip(sz: Sizes, seed: int, clock: CompileClock):
    """Phases a-e; returns the names of the phases that failed."""
    from repro.core import synthetic_timeseries
    rng = np.random.default_rng(seed)
    ecg = synthetic_timeseries(rng, sz.ecg_m)
    q, offs = make_batch(rng, ecg, sz.ecg_n, sz.ecg_nq, sz.planted)
    failed, offline = [], None
    phases = [
        ("a", lambda: phase_offline(clock, sz, ecg, q, offs)),
        ("b", lambda: phase_batch(clock, sz, rng)),
        ("c", lambda: phase_search(clock, sz, ecg, q, offs, need(offline))),
        ("d", lambda: phase_stream(clock, sz, rng, ecg, q, need(offline))),
        ("e", lambda: phase_served(clock, sz, rng,
                                   synthetic_timeseries(rng, sz.human_m))),
    ]
    for name, fn in phases:
        try:
            out = fn()
            if name == "a":
                offline = out
        except Exception:                              # noqa: BLE001
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
            failed.append(name)
    return failed


def need(offline):
    check(offline is not None, "offline phase (a) failed; nothing to "
                               "compare with")
    return offline


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------

def print_device_bytes(tag):
    import jax
    held = []
    for dev in jax.devices():
        st = dev.memory_stats() or {}
        held.append(st.get("peak_bytes_in_use", 0))
        print(f"[{tag}] {dev}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}",
              flush=True)
    check(all(b > 0 for b in held), f"{tag}: a device held no bytes")


def phase_sharded(clock, sz, ref, q):
    from repro.core import sdtw
    with Phase("f sharded", clock) as ph:
        one = [np.asarray(x) for x in sdtw(q, ref, return_spans=True)]
        for shape in ((1, 4), (4, 1)):
            out, dec = sdtw(q, ref, mesh_shape=shape, return_spans=True,
                            explain=True)
            ph.decision(dec.impl, dec.source,
                        f"mesh_shape={shape} config={dec.config}")
            for name, g, w in zip(("distance", "start", "end"), out, one):
                check(bool(np.array_equal(np.asarray(g), w)),
                      f"mesh {shape}: {name} differs from one device")
            ph.say(f"check mesh {shape} == one device: {len(q)} queries "
                   "equal (distance, start, end)")
            print_device_bytes(ph.tag)
            del out


def phase_served4(clock, sz, rng, ref):
    import jax
    from repro.serve import Router, RouterConfig
    with Phase("g served4", clock) as ph:
        # One request per dispatch, so that groups are in flight on
        # several devices at once; warming both request shapes on every
        # device lets the pool route to any of them without a compile.
        cfg = RouterConfig(devices="all", max_window_requests=1)
        with Router(cfg) as router:
            q = list(ref[:SERVED_NQ * sz.human_n].reshape(SERVED_NQ, -1))
            for spans in (False, True):
                warmed = router.warmup(queries=q, reference=ref,
                                       return_spans=spans)
            ph.decision("pallas", "structural",
                        f"({warmed} devices warmed)")
            n, on_device = serve_traffic(
                router, rng, ref, sz.human_n, sz.served4_clients,
                sz.served4_requests, "served4")
        want = sz.served4_clients * sz.served4_requests
        check(n == want, f"served {n} of {want} requests")
        ph.say(f"check 4-device served == one-device offline: {n}/{want} "
               f"requests equal; answers per device: {on_device}")
        check(len(jax.devices()) == 4, "expected four devices")
        print_device_bytes(ph.tag)


def run_four_chips(sz: Sizes, seed: int, clock: CompileClock):
    from repro.core import synthetic_timeseries
    rng = np.random.default_rng(seed)
    ecg = synthetic_timeseries(rng, sz.ecg_m)
    q, _ = make_batch(rng, ecg, sz.ecg_n, sz.sharded_nq, sz.planted)
    failed = []
    for name, fn in (
            ("f", lambda: phase_sharded(clock, sz, ecg, q)),
            ("g", lambda: phase_served4(
                clock, sz, rng, synthetic_timeseries(rng, sz.human_m)))):
        try:
            fn()
        except Exception:                              # noqa: BLE001
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
            failed.append(name)
    return failed


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded and 4-device Router "
                         "phases (needs four chips)")
    args = ap.parse_args(argv)

    try:
        import jax
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: cannot import the program: {exc}",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"device {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing "
              "was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    sz = Sizes()
    t0 = time.perf_counter()
    if args.chips == 4:
        failed = run_four_chips(sz, args.seed, clock)
    else:
        failed = run_one_chip(sz, args.seed, clock)
    total_c, total_h = clock.read()
    print(f"total compile_s={total_c:.3f} cache_hits={total_h} "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
