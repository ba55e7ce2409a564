"""Online sDTW monitoring over the chunk-carry protocol.

A ``StreamSession`` turns the engine's offline chunk loop inside out: the
*reference* is no longer a materialized array but an unbounded sequence of
chunks (an ECG electrode, a seismometer, a power meter — the paper's
continuous-monitoring workloads, §I/§V). The session holds a batch of
(possibly ragged) queries plus their DP carries — exactly the
``(boundary column[, start lane], best)`` tuples of
``repro.core.sdtw.sdtw_carry_init`` and, in match mode, the
``repro.core.topk`` heap — and ``session.feed(chunk)`` advances every
query by that chunk through the *same* ``sdtw_rowscan_chunk`` /
``sdtw_pallas`` code paths the offline engine runs. Because the carry
protocol is already chunk-size-invariant, any partition of the reference
fed through a session reproduces ``engine.sdtw`` distances, spans and
top-K *bitwise* (int32) — the differential property ``tests/test_stream.py``
enforces. On the Pallas impl, top-K heaps, threshold alerts and online
pruning all consume the kernel's in-kernel last-row capture (the per-tile
candidate row), folded with the identical ``topk_fold_lastrow`` merge the
rowscan path uses, so both impls produce the same bits; only per-query
exclusion zones still require ``impl='rowscan'``.

Mechanics that make streaming practical:

  * **One compiled shape per tile.** Fed chunks are buffered and the DP
    advances in fixed ``chunk``-sized tiles; the final partial tile is
    right-padded and masked via the DP's global-position ban
    (``m_total``), with the boundary column extracted at the *true* last
    column (the ``clen`` lane of ``sdtw_rowscan_chunk`` / the Pallas
    kernel's traced ``ref_len``) so a flushed session can keep streaming.
    Feed granularity is therefore decoupled from compile granularity.
  * **Online pruning.** With ``prune=True`` the session computes each
    tile's [min, max] envelope as it arrives, extends the shared
    ``EnvelopeCache`` under ``(ref_key, chunk)`` (an offline
    ``search_topk`` against the materialized reference later *hits* that
    entry), and runs the LB_Kim/LB_Keogh cascade against the current
    heap thresholds — a tile no query can improve on is skipped without
    touching the DP. Skipped tiles break the continuous carry, so — as in
    ``repro.search`` — surviving tiles are scored from a fresh carry
    warmed by a ``halo`` of buffered left-context tiles; the same
    ``span_cap`` caveat applies, and the admissibility of the bounds
    makes the pruned heap equal to the exact streamed heap whenever no
    relevant match's span exceeds the cap.
  * **Threshold alerts.** ``alert_threshold`` watches the per-tile
    candidate row (the cost of a match *ending* at each arriving sample):
    any query whose candidate drops to ``<= alert_threshold`` fires an
    ``AlertEvent`` (appended to ``session.alerts`` and passed to the
    ``on_alert`` callback) — feed anomaly templates as queries and the
    session becomes an online anomaly detector.
  * **Many streams, one launch per tile.** ``streams=S`` opens one
    session over S independent streams (patients, sensors) that share
    the query set and arrive aligned: ``feed`` takes ``(S, samples)``.
    The query rows are stacked stream-major (S · nq rows, each stream's
    carries and heaps its own rows), each stream's tile is repeated for
    its queries as a per-row reference, and one jitted step per tile
    advances every stream through one kernel launch. Alerts are reduced
    on the device to one (hits, distance, end, start) per row and
    fetched once per tile; ``results()`` gains a leading stream axis and
    every ``AlertEvent`` names its ``stream``. Each stream's answers
    equal those of its own one-stream session, bitwise.
  * **Fault tolerance.** ``session.snapshot()`` returns a flat dict of
    numpy arrays (``np.savez``-able as-is); ``StreamSession.restore``
    rebuilds a session that continues bit-for-bit where the original
    would have — kill the process mid-stream, restore, keep feeding.

Results are read non-destructively: ``session.results()`` applies the
buffered tail to a *copy* of the carry, so polling mid-stream never
perturbs the tile alignment of the live session. ``session.flush()``
pushes the tail through destructively (the carry stays exact thanks to
``clen``); in pruned mode a flush is terminal, because a partial tile
breaks the halo-group alignment the pruning windows assume.

Exactness notes: distances, spans and the top-1 match are exact for every
feed partition and any interleaving of ``flush()`` calls. The k > 1 heap
inherits the documented greedy-merge semantics of the offline chunked
path: it is bitwise-reproducible for a given tile size and equals the
offline heap when tile boundaries match. A **mid-stream** ``flush()``
(exact mode, then feeding continues) shifts every later tile boundary,
as if the offline call had used a different chunking — the k > 1 heap
beyond top-1 may then legitimately differ from the aligned-boundary
result, so the first ``feed()`` after such a flush on a k > 1 session
raises a loud ``RuntimeWarning`` (``results()`` polls the tail without
moving boundaries and never warns; k = 1 / span / plain sessions stay
exact and stay silent). Pruned-mode flushes are terminal and cannot
shift anything.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import warnings
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import engine as engine_mod
from repro.core.distances import INT_FAR, accum_dtype, big
from repro.core.request import StreamRequest
from repro.core.sdtw import (default_excl_zone, sdtw_carry_init,
                             sdtw_chunk_batch, sdtw_chunk_batch_topk,
                             topk_fold_lastrow)
from repro.core.topk import topk_init
from repro.search import cache as cache_mod
from repro.search.lower_bounds import chunk_envelope, lb_cascade
from repro.search.search import DEFAULT_SPAN_FACTOR, _pruned_chunk_step

#: Default DP tile size — the engine's streaming default.
DEFAULT_STREAM_CHUNK = engine_mod.DEFAULT_CHUNK

#: Version 2 added ``streams``; a version-1 snapshot is a one-stream one.
_SNAP_VERSION = 2


@dataclasses.dataclass(frozen=True)
class AlertEvent:
    """One threshold crossing: query ``query`` matched stream ``stream``
    at cost ``distance`` ending at global sample ``end`` (span start
    ``start``; -1 when the session does not track starts). ``hits``
    counts every sub-threshold end column inside the triggering tile
    ``[tile_start, tile_end)``; the reported (distance, end) is the best
    (leftmost on ties)."""
    query: int
    distance: float
    start: int
    end: int
    tile_start: int
    tile_end: int
    hits: int
    stream: int = 0


@dataclasses.dataclass
class StreamResult:
    """Streamed match state after ``samples`` reference samples.

    ``distances`` is (nq,) — or (nq, k) in top-K mode, best first,
    BIG/-1-padded like ``repro.core.topk`` — with a leading stream axis
    in a many-stream session. ``positions``/``starts`` are
    present when the session tracks ends/spans. Tile counters are
    per-*tile* across the whole (possibly multi-bucket) batch —
    ``tiles_pruned + tiles_processed == tiles_total`` always, unlike
    ``SearchResult``'s per-bucket chunk counters."""
    distances: object
    positions: object = None
    starts: object = None
    samples: int = 0
    tiles_total: int = 0
    tiles_pruned_kim: int = 0
    tiles_pruned_keogh: int = 0
    tiles_processed: int = 0

    @property
    def tiles_pruned(self) -> int:
        return self.tiles_pruned_kim + self.tiles_pruned_keogh

    @property
    def spans(self):
        """Stacked (start, end) spans, shape (..., 2)."""
        if self.starts is None or self.positions is None:
            raise ValueError("this session does not track spans — open it "
                             "with return_spans=True (or top_k=/prune=)")
        return np.stack([np.asarray(self.starts), np.asarray(self.positions)],
                        axis=-1)


@dataclasses.dataclass
class _Bucket:
    """One padded query bucket and its carry through the stream. Its
    rows are the bucket's ``nb`` queries once per stream, stream-major."""
    idxs: List[int]
    queries: jnp.ndarray        # (streams * nb, blen)
    qlens: jnp.ndarray          # (streams * nb,)
    lo: jnp.ndarray             # (streams * nb,) banned-range lower bounds
    hi: jnp.ndarray
    zone: jnp.ndarray           # (streams * nb,) top-K suppression radii
    carry: tuple                # chunk carry (+ heap in match mode)
    halo: int = 0               # pruned mode: left-context tiles
    thr: Optional[np.ndarray] = None  # pruned mode: per-query k-th best


def _pallas_step(queries, tile, qlens, kcarry, heap, j0, clen, zone, *,
                 metric, block_q, block_m, scan_scheme, row_tile, k,
                 excl_span, track, want_lastrow, with_heap):
    """One streamed tile through the Pallas kernel: advance the kernel
    chunk carry and — when the session consumes candidate rows — fold the
    in-kernel last-row capture into the top-K heap with the identical
    per-tile ``topk_merge`` the rowscan path runs, so pallas sessions
    reproduce the offline chunked heap bitwise (int32). Knobs left
    ``None`` come from the tuner, keyed on the call's shape."""
    from repro.kernels.sdtw import sdtw_pallas
    out = sdtw_pallas(queries, tile, qlens, metric, block_q=block_q,
                      block_m=block_m, carry=kcarry, return_carry=True,
                      ref_offset=j0, ref_len=clen, track_start=track,
                      scan_scheme=scan_scheme, row_tile=row_tile,
                      return_lastrow=want_lastrow, tune="model")
    if not want_lastrow:
        _, kc = out
        return kc, None, None
    if track:
        _, kc, lrow, lstart = out
    else:
        _, kc, lrow = out
        lstart = None
    if with_heap:
        heap = topk_fold_lastrow(heap, lrow, lstart, j0, k, zone,
                                 excl_span)
        return kc + tuple(heap), lrow, lstart
    return kc, lrow, lstart


def _tile_alerts(lrow, lstart, clen, thr):
    """Each row's alert for one tile, reduced on the device: ``(hits,
    distance, column, start)`` — the count of the tile's columns whose
    candidate cost is at most ``thr``, the least such cost, the leftmost
    column attaining it, and its span start (-1 untracked). A row with no
    hit reads ``hits == 0``."""
    cols = jnp.arange(lrow.shape[1], dtype=jnp.int32)[None, :]
    hit = (lrow <= thr) & (cols < clen)
    hits = jnp.sum(hit, axis=1, dtype=jnp.int32)
    fill = (jnp.iinfo(lrow.dtype).max
            if jnp.issubdtype(lrow.dtype, jnp.integer) else jnp.inf)
    dist = jnp.min(jnp.where(hit, lrow, fill), axis=1)
    at = hit & (lrow == dist[:, None])
    col = jnp.min(jnp.where(at, cols, lrow.shape[1]), axis=1)
    if lstart is None:
        start = jnp.full_like(col, -1)
    else:
        start = jnp.min(jnp.where(at & (cols == col[:, None]), lstart,
                                  INT_FAR), axis=1)
    return hits, dist, col, start


@functools.partial(jax.jit, static_argnames=("metric", "k", "excl_span",
                                             "track", "lastrow"))
def _heap_step(queries, tile, qlens, carry, j0, m_total, clen, lo, hi, zone,
               *, metric, k, excl_span, track, lastrow):
    out = sdtw_chunk_batch_topk(queries, tile, qlens, carry, j0, m_total,
                                metric, lo, hi, k, zone, excl_span, track,
                                clen=clen, return_lastrow=lastrow)
    if not lastrow:
        return out, None, None
    if track:
        return out[:6], out[6], out[7]
    return out[:5], out[5], None


@functools.partial(jax.jit, static_argnames=(
    "impl", "metric", "block_q", "block_m", "scan_scheme", "row_tile", "k",
    "excl_span", "track", "want_lastrow", "with_heap", "alert"))
def _exact_step(queries, tile, qlens, carry, j0, clen, lo, hi, zone, thr, *,
                impl, metric, block_q, block_m, scan_scheme, row_tile, k,
                excl_span, track, want_lastrow, with_heap, alert):
    """One exact-mode tile for one bucket, as one jitted step: advance the
    carry and, with ``alert``, reduce the tile's candidate rows to each
    row's alert (``_tile_alerts``). A 2-D ``tile`` (streams, C) is one
    tile per stream: each is repeated for its stream's query rows (which
    are stream-major) as a per-row reference."""
    if tile.ndim == 2:
        tile = jnp.repeat(tile, queries.shape[0] // tile.shape[0], axis=0)
    if impl == "pallas":
        kc = carry[:-3] if with_heap else carry
        heap = carry[-3:] if with_heap else None
        new, lrow, lstart = _pallas_step(
            queries, tile, qlens, kc, heap, j0, clen, zone, metric=metric,
            block_q=block_q, block_m=block_m, scan_scheme=scan_scheme,
            row_tile=row_tile, k=k, excl_span=excl_span, track=track,
            want_lastrow=want_lastrow, with_heap=with_heap)
    elif with_heap:
        new, lrow, lstart = _heap_step(
            queries, tile, qlens, carry, j0, j0 + clen, clen, lo, hi, zone,
            metric=metric, k=k, excl_span=excl_span, track=track,
            lastrow=want_lastrow)
    else:
        new = sdtw_chunk_batch(queries, tile, qlens, carry, j0, j0 + clen,
                               metric, lo, hi, clen=clen)
    if not alert:
        return new, None
    return new, _tile_alerts(lrow, lstart, clen, thr)


class StreamSession:
    """Online sDTW monitor: a query batch streamed against an unbounded
    reference, one ``feed()`` at a time. See the module docstring for the
    protocol; ``engine.stream()`` is the front door."""

    def __init__(self, queries, *, qlens=None, metric: str = "abs_diff",
                 chunk: Optional[int] = None, impl: str = "rowscan",
                 top_k: Optional[int] = None, excl_zone=None,
                 excl_mode: str = "end", return_spans: bool = False,
                 return_positions: bool = False,
                 excl_lo=None, excl_hi=None,
                 prune: bool = False, span_cap: Optional[int] = None,
                 alert_threshold=None,
                 on_alert: Optional[Callable[[AlertEvent], None]] = None,
                 cache: Optional[cache_mod.EnvelopeCache] = None,
                 ref_key=None, block_q: Optional[int] = None,
                 block_m: Optional[int] = None, streams: int = 1):
        if impl not in ("rowscan", "pallas"):
            raise ValueError(f"impl must be 'rowscan' or 'pallas' for a "
                             f"stream session, got {impl!r}")
        # The session-argument checks live with the shared validator in
        # repro.core.request — one source for engine.stream(), the serve
        # tier, and direct construction, so the rules cannot drift.
        StreamRequest(
            queries=queries, qlens=qlens, metric=metric, impl=impl,
            chunk=chunk, top_k=top_k, excl_zone=excl_zone,
            excl_mode=excl_mode, return_spans=return_spans,
            return_positions=return_positions, excl_lo=excl_lo,
            excl_hi=excl_hi, prune=prune, span_cap=span_cap,
            alert_threshold=alert_threshold, on_alert=on_alert,
            cache=cache, ref_key=ref_key, block_q=block_q,
            block_m=block_m, streams=streams).validate_session()

        self.metric = metric
        self.impl = impl
        self.chunk = int(DEFAULT_STREAM_CHUNK if chunk is None else chunk)
        self.top_k = top_k
        self.excl_mode = excl_mode
        self.return_spans = bool(return_spans)
        self.return_positions = bool(return_positions)
        self.prune = bool(prune)
        self.alert_threshold = (None if alert_threshold is None
                                else float(alert_threshold))
        self.on_alert = on_alert
        self.ref_key = ref_key
        self.cache = cache_mod.DEFAULT_CACHE if cache is None else cache
        self.block_q = block_q
        self.block_m = block_m
        self.streams = int(streams)
        self.alerts: List[AlertEvent] = []

        self._derive_modes()
        self._dtype = None           # pinned by the first feed

        # --- bucket the query batch (ragged lists via the engine rules) --
        self._ragged = isinstance(queries, (list, tuple))
        if self._ragged:
            if qlens is not None:
                raise ValueError("qlens is implied by ragged (list) queries")
            qs = [np.asarray(q) for q in queries]
            if not qs:
                raise ValueError("need at least one query")
            self._nq = len(qs)
            self._single = False
            buckets = engine_mod.bucketize([len(q) for q in qs])
            bucket_arrays = []
            for blen, idxs in buckets.items():
                padded, lens = engine_mod.pad_ragged_bucket(qs, idxs, blen)
                bucket_arrays.append((idxs, padded, lens))
        else:
            q2 = np.asarray(queries)
            self._single = q2.ndim == 1
            if self._single:
                q2 = q2[None, :]
            self._nq = q2.shape[0]
            lens = (np.full((self._nq,), q2.shape[1], np.int32)
                    if qlens is None else np.asarray(qlens, np.int32))
            bucket_arrays = [(list(range(self._nq)), q2, lens)]

        lo_all = np.asarray(engine_mod._normalize_excl(excl_lo, self._nq))
        hi_all = np.asarray(engine_mod._normalize_excl(excl_hi, self._nq))
        if excl_zone is None:
            zone_all = None
        else:
            zone_all = np.broadcast_to(
                np.asarray(excl_zone, np.int32), (self._nq,))

        def per_stream(a):
            """A bucket's per-query array, once per stream, stream-major."""
            a = np.asarray(a)
            return jnp.asarray(np.tile(a, (self.streams,) + (1,) *
                                       (a.ndim - 1)))

        self._buckets: List[_Bucket] = []
        span_caps = []
        for idxs, padded, lens in bucket_arrays:
            n = padded.shape[1]
            if zone_all is None:
                zone = (np.asarray(default_excl_zone(lens))
                        if excl_mode == "end"
                        else np.zeros((len(idxs),), np.int32))
            else:
                zone = zone_all[np.asarray(idxs)]
            cap = (DEFAULT_SPAN_FACTOR * n if span_cap is None
                   else int(span_cap))
            span_caps.append(cap)
            halo = max(1, -(-cap // self.chunk)) if self.prune else 0
            b = _Bucket(idxs=list(idxs), queries=per_stream(padded),
                        qlens=per_stream(np.asarray(lens, np.int32)),
                        lo=per_stream(lo_all[np.asarray(idxs)]),
                        hi=per_stream(hi_all[np.asarray(idxs)]),
                        zone=per_stream(np.asarray(zone, np.int32)),
                        carry=None, halo=halo)
            b.carry = self._fresh_carry(b)
            if self.prune:
                b.thr = np.full((len(idxs),), np.inf)
            self._buckets.append(b)
        self.span_cap = max(span_caps)
        self._max_halo = max(b.halo for b in self._buckets)

        # --- stream state ------------------------------------------------
        self._buf = np.zeros(self._lead + (0,), np.int32)
        self._offset = 0             # samples advanced through the DP
        self._finalized = False
        self._flush_shift_pending = False   # mid-stream flush happened
        self._ring: List[np.ndarray] = []   # pruned mode: last halo tiles
        self._env_tail: List[tuple] = []    # pruned mode: trailing envelopes
        # Full streamed envelope (accumulator dtype, one entry per tile) —
        # what cache.extend() has received so far. Snapshotted, so a
        # restore into a *fresh* cache can install the whole prefix
        # instead of extending from mid-stream (which would leave a
        # truncated envelope for offline reuse).
        self._env_mins: List[np.ndarray] = []
        self._env_maxs: List[np.ndarray] = []
        self.tiles_total = 0
        self.tiles_pruned_kim = 0
        self.tiles_pruned_keogh = 0
        self.tiles_processed = 0
        self._reset_counters()

    def _reset_counters(self):
        self.launches = 0            # jitted tile steps run
        self.streams_advanced = 0    # streams moved on by a tile, summed
        self.alerts_fired = 0
        self.lanes_fed = 0           # reference samples of kernel launches
        self.lanes_launched = 0      # the padded tile widths they ran

    def counters(self) -> dict:
        """Telemetry since the session was opened or restored: device
        steps launched, streams advanced (one per stream per tile), tiles
        and alerts; and, summed over kernel launches, the reference
        samples each was fed and the tile width it ran (the tile rounded
        up to whole ``block_m`` blocks), whose gap is lane padding."""
        return {"launches": self.launches,
                "streams_advanced": self.streams_advanced,
                "tiles": self.tiles_total, "alerts": self.alerts_fired,
                "lanes_fed": self.lanes_fed,
                "lanes_launched": self.lanes_launched}

    @property
    def _lead(self) -> tuple:
        """Leading shape of fed data: ``(streams,)``, or none for one."""
        return () if self.streams == 1 else (self.streams,)

    # ------------------------------------------------------------------
    # carry plumbing
    # ------------------------------------------------------------------

    def _derive_modes(self):
        """The mode lattice, mirroring sdtw_chunked: a heap rides the
        carry as soon as any positional output (or an alert feed) is
        consumed; the start lane only when spans/span-suppression need
        it. Derived in exactly one place so ``restore()`` can never
        unpack carries under a different layout than the session that
        snapshotted them.

        The pallas kernel tracks the top-1 (value, end, start) natively
        in its own carry, so a pallas session appends the heap triple to
        the kernel carry only for a real top-K, and asks the kernel for
        its in-kernel last-row capture exactly when a candidate row is
        consumed (top-K folding or threshold alerts)."""
        self._k = 1 if self.top_k is None else self.top_k
        if self.impl == "pallas":
            self._wants_heap = self.top_k is not None
            self._want_lastrow = (self.top_k is not None
                                  or self.alert_threshold is not None)
        else:
            self._wants_heap = (self.top_k is not None or self.return_spans
                                or self.return_positions
                                or self.alert_threshold is not None)
            self._want_lastrow = self.alert_threshold is not None
        self._track = self.return_spans or self.excl_mode == "span"

    def _acc(self, b: _Bucket):
        ref_dtype = self._dtype if self._dtype is not None \
            else np.asarray(b.queries).dtype
        return accum_dtype(jnp.result_type(np.asarray(b.queries).dtype,
                                           ref_dtype))

    def _fresh_carry(self, b: _Bucket):
        nb, n = b.queries.shape
        acc = self._acc(b)
        if self.prune:
            # Pruned mode scores surviving tiles from fresh halo-warmed
            # carries (on either impl) — the session carry is the heap.
            return topk_init(nb, self._k, acc)
        if self.impl == "pallas":
            if self._dtype is None:
                return None          # accumulator unknown until first feed
            from repro.kernels.sdtw import pallas_carry_init
            kc = pallas_carry_init(nb, n, acc, track_start=self._track)
            if self._wants_heap:
                return kc + topk_init(nb, self._k, acc)
            return kc
        if self._wants_heap:
            return (sdtw_carry_init(nb, n, acc, track_start=self._track)
                    + topk_init(nb, self._k, acc))
        return sdtw_carry_init(nb, n, acc)

    # ------------------------------------------------------------------
    # feeding
    # ------------------------------------------------------------------

    @property
    def samples_seen(self) -> int:
        """Reference samples fed so far per stream (including the
        buffered tail)."""
        return self._offset + int(self._buf.shape[-1])

    def feed(self, data) -> "StreamSession":
        """Append reference samples — ``(samples,)``, or ``(streams,
        samples)`` in a many-stream session; advance the DP by every
        whole tile."""
        with TraceAnnotation("stream.tick"):
            return self._feed(data)

    def _feed(self, data) -> "StreamSession":
        if self._finalized:
            raise RuntimeError("session is finalized (a pruned-mode flush "
                               "is terminal); snapshot/restore to branch "
                               "earlier")
        data = np.asarray(data)
        if data.shape[:-1] != self._lead or data.ndim != len(self._lead) + 1:
            want = ("a 1-D chunk" if not self._lead else
                    f"a ({self.streams}, samples) chunk, one row per stream")
            raise ValueError(f"feed() takes {want}, got shape {data.shape}")
        if data.shape[-1] == 0:
            return self
        if self._flush_shift_pending:
            self._flush_shift_pending = False
            if self.top_k is not None and self._k > 1:
                warnings.warn(
                    "feeding a k>1 session after a mid-stream flush(): the "
                    "partial tile shifted every later merge boundary, so "
                    "heap entries beyond top-1 may differ from an "
                    "aligned-boundary (offline or unflushed) run — the "
                    "top-1 distance/span stays exact. Poll results() "
                    "instead of flush() to read the tail without moving "
                    "boundaries.", RuntimeWarning, stacklevel=3)
        if self._dtype is None:
            self._dtype = data.dtype
            self._buf = np.zeros(self._lead + (0,), data.dtype)
            if self._offset == 0:
                # The carry's accumulator dtype depends on the stream's —
                # rebuild the untouched fresh carries now that it is known.
                for b in self._buckets:
                    b.carry = self._fresh_carry(b)
        elif data.dtype != self._dtype:
            raise ValueError(f"stream dtype changed mid-flight: "
                             f"{self._dtype} -> {data.dtype}")
        self._buf = np.concatenate([self._buf, data], axis=-1)
        while self._buf.shape[-1] >= self.chunk:
            tile, self._buf = (self._buf[..., :self.chunk],
                               self._buf[..., self.chunk:])
            self._advance(tile, self.chunk)
        return self

    def _padded_tail(self) -> np.ndarray:
        """The buffered tail right-padded to one tile."""
        padded = np.zeros(self._lead + (self.chunk,), self._buf.dtype)
        padded[..., :self._buf.shape[-1]] = self._buf
        return padded

    def flush(self) -> "StreamSession":
        """Destructively push the buffered tail through the DP.

        Exact mode keeps streaming afterwards (the carry exits at the true
        boundary); pruned mode finalizes the session — a partial tile
        breaks the halo-window alignment the bounds assume."""
        tail = int(self._buf.shape[-1])
        if tail:
            padded = self._padded_tail()
            self._buf = self._buf[..., :0]
            self._advance(padded, tail)
            if self.prune:
                self._finalized = True
            elif tail % self.chunk:
                # Exact mode keeps streaming, but the partial tile moved
                # every later tile boundary — the next feed() warns when
                # a k>1 heap rides this session (see module docstring).
                self._flush_shift_pending = True
        return self

    def _advance(self, tile_np: np.ndarray, clen: int):
        """Advance every bucket of every stream by one (possibly
        right-padded) tile."""
        j0 = self._offset
        if self.prune:
            self._advance_pruned(tile_np, clen, j0)
        else:
            tile = jnp.asarray(tile_np)
            alerts = []
            for b in self._buckets:
                with TraceAnnotation("stream.launch"):
                    b.carry, alert, blocks = self._step_exact(
                        b, tile, j0, clen, b.carry,
                        alert=self.alert_threshold is not None)
                self.launches += 1
                if blocks is not None:
                    bm = blocks[1]
                    self.lanes_fed += clen
                    self.lanes_launched += -(-tile.shape[-1] // bm) * bm
                alerts.append(alert)
            if self.alert_threshold is not None:
                self._emit_alerts(alerts, j0, clen)
            self.tiles_processed += 1      # exact mode runs every tile
        self.tiles_total += 1
        self.streams_advanced += self.streams
        self._offset += clen

    def _step_exact(self, b: _Bucket, tile, j0: int, clen: int, carry, *,
                    alert: bool = False):
        """One exact-mode tile for one bucket — pure in ``carry``. Returns
        the new carry, with ``alert`` the rows' device-side alerts (else
        None), and the kernel's ``(block_q, block_m, scan_scheme,
        row_tile)`` (None off the kernel)."""
        thr = self._device_threshold(self._acc(b)) if alert else None
        blocks = self._launch_blocks(b, tile)
        bq, bm, scheme, rt = (blocks if blocks is not None
                              else (self.block_q, self.block_m, None, None))
        new, alerts = _exact_step(
            b.queries, tile, b.qlens, carry, jnp.int32(j0),
            jnp.int32(clen), b.lo, b.hi, b.zone, thr, impl=self.impl,
            metric=self.metric, block_q=bq, block_m=bm, scan_scheme=scheme,
            row_tile=rt, k=self._k, excl_span=self.excl_mode == "span",
            track=self._track, want_lastrow=self._want_lastrow,
            with_heap=self._wants_heap, alert=alert)
        return new, alerts, blocks

    def _launch_blocks(self, b: _Bucket, tile):
        """The kernel block of one tile step, resolved here with the
        arguments ``sdtw_pallas`` would resolve it with (the session's
        explicit ``block_q``/``block_m`` win, the tuner fills the rest)
        and handed to the launch whole, so the step runs exactly this
        block. None on the rowscan path."""
        if self.impl != "pallas":
            return None
        from repro.kernels.sdtw import interpret_mode, resolve_blocks
        rows, n = b.queries.shape
        return resolve_blocks(
            rows, tile.shape[-1], self.block_q, self.block_m, None, None,
            interpret_mode(), n=n, metric=self.metric,
            dtype=str(jnp.result_type(b.queries, tile)), tune="model",
            span=self._track, lastrow=self._want_lastrow,
            rowref=tile.ndim == 2)

    def _device_threshold(self, acc):
        """The alert threshold in the accumulator's dtype, compared on the
        device as NumPy compares an ``acc`` cost with the Python float:
        exactly for integer costs (so floored), in ``acc`` for floating
        ones."""
        thr = self.alert_threshold
        if np.issubdtype(acc, np.integer):
            info = np.iinfo(acc)
            thr = np.clip(np.floor(thr), info.min, info.max)
        return jnp.asarray(thr, acc)

    def _emit_alerts(self, alerts, j0: int, clen: int):
        """Fetch a tile's device-side alerts, every bucket's at once, and
        record one ``AlertEvent`` per row that has a hit."""
        with TraceAnnotation("stream.alerts"):
            fetched = jax.device_get(alerts)
            for b, (hits, dist, col, start) in zip(self._buckets, fetched):
                nb = len(b.idxs)
                for row in np.flatnonzero(hits):
                    stream, i = divmod(int(row), nb)
                    ev = AlertEvent(
                        query=b.idxs[i], distance=dist[row].item(),
                        start=int(start[row]), end=j0 + int(col[row]),
                        tile_start=j0, tile_end=j0 + clen,
                        hits=int(hits[row]), stream=stream)
                    self.alerts.append(ev)
                    self.alerts_fired += 1
                    if self.on_alert is not None:
                        self.on_alert(ev)

    # ------------------------------------------------------------------
    # online pruning (LB cascade against the live heap thresholds)
    # ------------------------------------------------------------------

    def _advance_pruned(self, tile_np: np.ndarray, clen: int, j0: int):
        env_mins, env_maxs = chunk_envelope(jnp.asarray(tile_np[:clen]),
                                            self.chunk)
        if self.ref_key is not None:
            # The full-prefix copy exists only for the cache handoff (and
            # its snapshot/restore story) — a keyless session keeps just
            # the trailing bound window, so unbounded streams stay O(halo).
            self._env_mins.append(np.asarray(env_mins))
            self._env_maxs.append(np.asarray(env_maxs))
            self.cache.extend((self.ref_key, False), self.chunk,
                              self._env_mins[-1], self._env_maxs[-1],
                              at=self.tiles_total)
        self._env_tail.append((float(np.asarray(env_mins)[0]),
                               float(np.asarray(env_maxs)[0])))
        self._env_tail = self._env_tail[-(self._max_halo + 1):]
        # Per-*tile* telemetry (tiles_pruned + tiles_processed ==
        # tiles_total even for ragged multi-bucket batches): the tile
        # counts as processed if any bucket's DP ran, else it is
        # attributed to the cheapest bound that discharged every bucket.
        decisions = []
        for b in self._buckets:
            decision, heap = self._step_pruned(b, tile_np, clen, j0,
                                               (b.thr, b.carry))
            decisions.append(decision)
            if decision == "processed":
                self.launches += 1
                b.carry = heap
                b.thr = np.asarray(heap[0][:, -1], np.float64)
        if "processed" in decisions:
            self.tiles_processed += 1
        elif "keogh" in decisions:
            self.tiles_pruned_keogh += 1
        else:
            self.tiles_pruned_kim += 1
        # The halo ring keeps raw context for future surviving tiles.
        self._ring.append(np.asarray(tile_np))
        self._ring = self._ring[-max(1, self._max_halo):]

    def _tile_bounds(self, b: _Bucket, win):
        mins = jnp.asarray([w[0] for w in win], jnp.float32)
        maxs = jnp.asarray([w[1] for w in win], jnp.float32)
        kim, keogh = lb_cascade(b.queries, b.qlens, mins, maxs, b.halo,
                                self.metric)
        return np.asarray(kim)[:, -1], np.asarray(keogh)[:, -1]

    def _step_pruned(self, b: _Bucket, tile_np, clen: int, j0: int, state):
        """Bound-check one tile for one bucket; score it if it survives.

        Pure in ``state = (thr, heap)`` — the peek path calls it with
        copies. Returns (decision, new_heap) with decision in
        {'kim', 'keogh', 'processed'}."""
        thr, heap = state
        win = self._env_tail[-(b.halo + 1):]
        kim, keogh = self._tile_bounds(b, win)
        if np.all(kim >= thr):
            return "kim", heap
        if np.all(keogh >= thr):
            return "keogh", heap
        group = np.zeros(((b.halo + 1) * self.chunk,), tile_np.dtype)
        ctx = self._ring[-b.halo:] if b.halo else []
        if ctx:
            ctx_flat = np.concatenate(ctx)
            group[b.halo * self.chunk - ctx_flat.shape[0]:
                  b.halo * self.chunk] = ctx_flat
        group[b.halo * self.chunk:] = tile_np
        hd, hp, hs = _pruned_chunk_step(
            b.queries, b.qlens, jnp.asarray(group), heap[0], heap[1],
            heap[2], jnp.int32(j0 - b.halo * self.chunk),
            jnp.int32(j0 + clen), b.lo, b.hi, b.zone, metric=self.metric,
            chunk=self.chunk, halo=b.halo, k=self._k,
            excl_span=self.excl_mode == "span",
            engine_impl=self.impl)
        return "processed", (hd, hp, hs)

    # ------------------------------------------------------------------
    # results
    # ------------------------------------------------------------------

    def results(self) -> StreamResult:
        """Current match state — *non-destructive*: the buffered tail is
        applied to a copy of the carry, so the live session's tile
        alignment is untouched and results() can be polled freely."""
        carries = {}
        tail = self._buf
        for bi, b in enumerate(self._buckets):
            carry = b.carry
            if tail.shape[-1]:
                padded = self._padded_tail()
                if self.prune:
                    # Peek with copies of (thr, heap); ring/cache untouched.
                    saved_env = list(self._env_tail)
                    env = chunk_envelope(jnp.asarray(tail), self.chunk)
                    self._env_tail = (saved_env
                                      + [(float(np.asarray(env[0])[0]),
                                          float(np.asarray(env[1])[0]))]
                                      )[-(self._max_halo + 1):]
                    try:
                        _, carry = self._step_pruned(
                            b, padded, int(tail.shape[0]), self._offset,
                            (b.thr, carry))
                    finally:
                        self._env_tail = saved_env
                else:
                    carry, _, _ = self._step_exact(
                        b, jnp.asarray(padded), self._offset,
                        int(tail.shape[-1]), carry)
            carries[bi] = carry
        return self._assemble(carries)

    def _assemble(self, carries) -> StreamResult:
        kk = self._k
        S = self.streams
        out_d = [None] * (S * self._nq)     # stream-major, like the rows
        out_p = [None] * (S * self._nq)
        out_s = [None] * (S * self._nq)
        wants_pos = (self._wants_heap or self.impl == "pallas") and \
            (self.top_k is not None or self.return_positions
             or self.return_spans)
        for bi, b in enumerate(self._buckets):
            carry = carries[bi]
            if self.prune:
                d, p, s = (np.asarray(x) for x in carry)
            elif self.impl == "pallas":
                if carry is None:
                    acc = self._acc(b)
                    nb = b.queries.shape[0]
                    d = np.full((nb, kk), big(acc), acc)
                    p = np.full((nb, kk), -1, np.int32)
                    s = np.full((nb, kk), -1, np.int32)
                elif self._wants_heap:
                    d, p, s = (np.asarray(x) for x in carry[-3:])
                else:
                    if self._track:
                        _, _, d, p, s = (np.asarray(x) for x in carry)
                    else:
                        _, d, p = (np.asarray(x) for x in carry)
                        s = np.full_like(p, -1)
                    d, p, s = d[:, None], p[:, None], s[:, None]  # (nb, 1)
            elif self._wants_heap:
                d, p, s = (np.asarray(x) for x in carry[-3:])
            else:
                d = np.asarray(carry[-1])[:, None]
                p = s = np.full_like(d, -1, dtype=np.int32)
            for row in range(d.shape[0]):
                stream, i = divmod(row, len(b.idxs))
                at = stream * self._nq + b.idxs[i]
                out_d[at], out_p[at], out_s[at] = d[row], p[row], s[row]
        dists, poss, starts = (np.stack(o).reshape((S, self._nq, -1))
                               for o in (out_d, out_p, out_s))
        if S == 1:                      # no stream axis
            dists, poss, starts = dists[0], poss[0], starts[0]
        if self.top_k is None:          # unstacked top-1 / plain
            dists, poss, starts = dists[..., 0], poss[..., 0], starts[..., 0]
        else:
            dists, poss, starts = (dists[..., :kk], poss[..., :kk],
                                   starts[..., :kk])
        if self._single:
            dists, poss, starts = ((x[0] if S == 1 else x[:, 0])
                                   for x in (dists, poss, starts))
        return StreamResult(
            distances=dists,
            positions=poss if wants_pos else None,
            starts=starts if (wants_pos and (self._track or self.prune))
            else None,
            samples=self.samples_seen,
            tiles_total=self.tiles_total,
            tiles_pruned_kim=self.tiles_pruned_kim,
            tiles_pruned_keogh=self.tiles_pruned_keogh,
            tiles_processed=self.tiles_processed)

    # ------------------------------------------------------------------
    # snapshot / restore (fault-tolerant serving)
    # ------------------------------------------------------------------

    def snapshot(self) -> dict:
        """Serialize the full session state as a flat dict of numpy
        arrays — ``np.savez(path, **snap)``-ready. ``restore()`` rebuilds
        a session that continues bit-for-bit."""
        meta = dict(
            version=_SNAP_VERSION, metric=self.metric, impl=self.impl,
            chunk=self.chunk, top_k=self.top_k, excl_mode=self.excl_mode,
            return_spans=self.return_spans,
            return_positions=self.return_positions, prune=self.prune,
            span_cap=self.span_cap,
            alert_threshold=self.alert_threshold,
            ref_key=self.ref_key if isinstance(self.ref_key, (str, int,
                                                              type(None)))
            else None,
            offset=self._offset, finalized=self._finalized,
            flush_shift=self._flush_shift_pending,
            block_q=self.block_q, block_m=self.block_m,
            dtype=None if self._dtype is None else np.dtype(
                self._dtype).name,
            nq=self._nq, single=self._single, ragged=self._ragged,
            streams=self.streams,
            tiles=[self.tiles_total, self.tiles_pruned_kim,
                   self.tiles_pruned_keogh, self.tiles_processed],
            env_tail=list(getattr(self, "_env_tail", [])),
            n_buckets=len(self._buckets),
            bucket_idxs=[b.idxs for b in self._buckets],
            bucket_halos=[b.halo for b in self._buckets],
            carry_lens=[0 if b.carry is None else len(b.carry)
                        for b in self._buckets],
            n_ring=len(self._ring),
        )
        snap = {"meta": np.array(json.dumps(meta)),
                "buffer": np.asarray(self._buf)}
        if self._env_mins:
            snap["env_mins"] = np.concatenate(self._env_mins)
            snap["env_maxs"] = np.concatenate(self._env_maxs)
        for t, tile in enumerate(self._ring):
            snap[f"ring{t}"] = np.asarray(tile)
        for bi, b in enumerate(self._buckets):
            snap[f"b{bi}_queries"] = np.asarray(b.queries)
            snap[f"b{bi}_qlens"] = np.asarray(b.qlens)
            snap[f"b{bi}_lo"] = np.asarray(b.lo)
            snap[f"b{bi}_hi"] = np.asarray(b.hi)
            snap[f"b{bi}_zone"] = np.asarray(b.zone)
            if b.thr is not None:
                snap[f"b{bi}_thr"] = np.asarray(b.thr)
            if b.carry is not None:
                for ci, leaf in enumerate(b.carry):
                    snap[f"b{bi}_carry{ci}"] = np.asarray(leaf)
        return snap

    @classmethod
    def restore(cls, snap, *, on_alert=None, cache=None,
                ref_key=None) -> "StreamSession":
        """Rebuild a session from ``snapshot()`` output (or a loaded
        ``np.load`` of it). ``on_alert``/``cache`` are not serialized —
        pass them again (nor are the alert log and ``counters()``, which
        start afresh); ``ref_key`` overrides the snapshotted key (e.g.
        when the cache identity changed across processes)."""
        meta = json.loads(str(np.asarray(snap["meta"])[()]))
        if meta["version"] not in (1, _SNAP_VERSION):
            raise ValueError(f"snapshot version {meta['version']} not "
                             f"supported (expected {_SNAP_VERSION})")
        self = cls.__new__(cls)
        self.metric = meta["metric"]
        self.impl = meta["impl"]
        self.chunk = meta["chunk"]
        self.top_k = meta["top_k"]
        self.excl_mode = meta["excl_mode"]
        self.return_spans = meta["return_spans"]
        self.return_positions = meta["return_positions"]
        self.prune = meta["prune"]
        self.span_cap = meta["span_cap"]
        self.alert_threshold = meta["alert_threshold"]
        self.ref_key = meta["ref_key"] if ref_key is None else ref_key
        self.cache = cache_mod.DEFAULT_CACHE if cache is None else cache
        self.on_alert = on_alert
        self.block_q = meta["block_q"]
        self.block_m = meta["block_m"]
        self.streams = meta.get("streams", 1)
        self.alerts = []
        self._reset_counters()
        self._derive_modes()
        self._nq = meta["nq"]
        self._single = meta["single"]
        self._ragged = meta["ragged"]
        self._offset = meta["offset"]
        self._finalized = meta["finalized"]
        self._flush_shift_pending = meta.get("flush_shift", False)
        self._dtype = (None if meta["dtype"] is None
                       else np.dtype(meta["dtype"]))
        (self.tiles_total, self.tiles_pruned_kim, self.tiles_pruned_keogh,
         self.tiles_processed) = meta["tiles"]
        self._env_tail = [tuple(e) for e in meta["env_tail"]]
        self._buf = np.asarray(snap["buffer"])
        if "env_mins" in snap:
            self._env_mins = [np.asarray(snap["env_mins"])]
            self._env_maxs = [np.asarray(snap["env_maxs"])]
            if self.ref_key is not None:
                # Install the snapshotted prefix so a fresh cache in a new
                # process sees the whole stream, not a mid-stream
                # continuation of an entry it never had — but never
                # truncate a live entry that is already further along
                # (e.g. restore() branching inside the original process).
                ck = (self.ref_key, False)
                cur = self.cache.peek(ck, self.chunk)
                if cur is None or (len(np.asarray(cur[0]))
                                   < len(self._env_mins[0])):
                    self.cache.put(ck, self.chunk, snap["env_mins"],
                                   snap["env_maxs"])
        else:
            self._env_mins, self._env_maxs = [], []
        self._ring = [np.asarray(snap[f"ring{t}"])
                      for t in range(meta["n_ring"])]
        self._buckets = []
        for bi in range(meta["n_buckets"]):
            ncar = meta["carry_lens"][bi]
            carry = (tuple(jnp.asarray(snap[f"b{bi}_carry{ci}"])
                           for ci in range(ncar)) if ncar else None)
            b = _Bucket(
                idxs=list(meta["bucket_idxs"][bi]),
                queries=jnp.asarray(snap[f"b{bi}_queries"]),
                qlens=jnp.asarray(snap[f"b{bi}_qlens"]),
                lo=jnp.asarray(snap[f"b{bi}_lo"]),
                hi=jnp.asarray(snap[f"b{bi}_hi"]),
                zone=jnp.asarray(snap[f"b{bi}_zone"]),
                carry=carry, halo=meta["bucket_halos"][bi],
                thr=(np.asarray(snap[f"b{bi}_thr"])
                     if f"b{bi}_thr" in snap else None))
            self._buckets.append(b)
        self._max_halo = max(b.halo for b in self._buckets)
        return self
