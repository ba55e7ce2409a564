"""Unified sDTW engine — the single front door every caller routes through.

``sdtw()`` hides four execution regimes behind one call:

  * ``rowscan`` / ``wavefront`` — the in-core JAX schedules of
    ``repro.core.sdtw`` (tropical associative scan vs the paper-faithful
    anti-diagonal wavefront).
  * ``pallas``  — the TPU kernel of ``repro.kernels.sdtw`` (interpret mode
    off-TPU).
  * ``chunked`` — reference streaming: the reference is processed in
    fixed-size tiles carrying only the O(N) boundary column between tiles
    (MATSA's inter-subarray pass gates, §III-B), so the paper's M≈1.8M ECG
    references run in bounded memory under one jitted shape.
  * ``sharded`` — the reference axis is sharded across devices
    (``repro.distributed.sdtw_sharded``); the chunk carry is exchanged
    between neighbouring devices with ``lax.ppermute``.

Dispatch rules (``impl="auto"``):

  1. ``mesh`` given (or ``impl="sharded"``)        → sharded driver.
  2. ``chunk`` given explicitly                    → chunked streaming.
  3. TPU backend and no exclusion zone             → Pallas kernel (its
     tile grid already streams arbitrary M).
  4. M ≥ ``CHUNK_THRESHOLD``                       → chunked streaming.
  5. M < 2·N (reference not much longer than query)→ wavefront (diagonal
     depth N+M-1 ≈ cheap; avoids the associative-scan constant).
  6. otherwise                                     → rowscan.

Rules 1–4 are *structural* (hard constraints); rules 5–6 are the legacy
``tune='off'`` heuristics.  Under the engine default ``tune='model'`` the
in-core choice comes from the ``repro.tune`` cost-model ranking (or a
tuning-table hit) instead — on measured CPU shapes that picks the
wavefront well beyond the ``M < 2N`` line — and the chunked / sharded /
pallas paths take their ``chunk`` / ``n_micro`` / block shapes from the
same oracle.  ``sdtw(..., explain=True)`` returns the
``repro.tune.DispatchDecision`` explaining what won and why.

``impl=`` is an escape hatch that forces any of the five paths. Forcing a
path makes argument precedence *explicit*: arguments that belong to a
different path are rejected instead of silently ignored —
``impl='rowscan'`` (or ``'wavefront'``) with ``mesh=`` or ``chunk=`` is a
``ValueError``, as is ``mesh=`` with any non-sharded forced impl. The one
deliberate combination is ``impl='pallas'`` with ``chunk=``: the reference
is streamed through the kernel's chunk-carry protocol *on the device*.
For references up to ``PALLAS_FUSED_MAX`` samples this is the single-
launch grid path (the kernel's own sequential tile dimension already
streams HBM→VMEM tile by tile, so one ``pallas_call`` covers any
device-resident reference and ``chunk`` is advisory); beyond it, the
reference is scanned in ``chunk``-sized statically-shaped slices inside
one jitted ``lax.scan`` (``_pallas_scan_streamed``) — the carry never
leaves the device and there is exactly one compiled executable regardless
of reference length or tail size (the tail slice is right-padded and
masked via the kernel's traced ``ref_len``). ``_pallas_host_loop`` keeps
the legacy one-launch-per-slice loop — not dispatched automatically, but
kept callable as the semantic reference the device-side paths are
differential-tested against, and for callers that must slice a
host-resident reference themselves; it pads the ragged tail to the
static ``chunk`` shape, so it too emits exactly one compiled executable.

Match spans: ``return_spans=True`` returns ``(dists, starts, ends)`` on
every path — the DP carries a start-pointer lane (each cell remembers the
row-0 reference column its best alignment began at, lexicographic
tie-break toward the smaller start; see ``repro.core.sdtw``), so the span
is exact and identical across all five regimes. ``engine.align()`` goes
one step further and recovers the full warping path by re-running the DP
inside the span window only (``repro.core.traceback``).

Top-K search mode: ``top_k=k`` returns the k best *match end positions*
per query, ``(dists (nq, k), positions (nq, k))`` — or
``(dists, starts, ends)`` with ``return_spans=True`` — best first, with
an exclusion zone (``excl_zone``, default: half of each query's true
length) keeping the matches non-trivially distinct;
``excl_mode='span'`` keys the suppression on span overlap instead of end
distance (default zone 0: reported events share no reference samples).
The heap rides the chunk boundary carry (streaming/sharded paths).
``return_positions=True`` alone returns the top-1 pair
``(dists (nq,), positions (nq,))`` and is supported on every path (the
Pallas kernel tracks the best end position in its carry).

The layers above compose this machinery rather than re-deriving it:
``repro.search.search_topk`` puts the LB cascade in front of the chunked
top-K path, and ``repro.search.profile.matrix_profile`` (with its
streaming twin ``repro.stream.StreamProfile``) runs the self-join matrix
profile — every sliding window of a series as a query batch against the
series itself, trivial matches banned via per-query ``excl_lo/excl_hi``
in sample units — returning motif pairs and top-K discords.

Ragged batches: a *list* of 1-D queries with mixed lengths is bucketed —
each query is padded up to the next power-of-two length (min
``MIN_BUCKET``) and queries sharing a bucket run as one batched call. The
compiled-shape count is therefore O(log max_len) across the process
lifetime instead of one shape per distinct query length.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .distances import accum_dtype, big
from .request import SdtwRequest, StreamRequest, resolve_mesh
from .sdtw import sdtw_batch, sdtw_chunked
from .traceback import AlignResult, DEFAULT_TRACE_CHUNK, traceback_path

CHUNK_THRESHOLD = 1 << 17   # auto-switch to streaming above this M
DEFAULT_CHUNK = 8192        # tile size for chunked/sharded streaming
MIN_BUCKET = 16             # smallest ragged-batch padded length

#: Largest reference (samples) the pallas+chunk path runs as one
#: single-launch kernel grid; longer references stream through the
#: device-side ``lax.scan`` of chunk-sized slices. 4M samples = 16 MB of
#: int32 — far below HBM, but the single-launch grid is unrolled per tile
#: at trace time, so the cap also bounds compile time.
PALLAS_FUSED_MAX = 1 << 22


def choose_impl_explained(nq: int, n: int, m: int, *,
                          backend: Optional[str] = None, mesh=None,
                          chunk: Optional[int] = None,
                          has_exclusion: bool = False,
                          top_k: Optional[int] = None, tune: str = "off",
                          metric: str = "abs_diff",
                          dtype: str = "int32") -> tuple:
    """``choose_impl`` with its reasoning: ``(impl, source, reason,
    candidates)`` where ``source``/``candidates`` follow
    ``repro.tune.DispatchDecision``.  The structural rules (mesh / top-K /
    explicit chunk / TPU / memory bound) are hard constraints and fire
    before any scoring; with ``tune != 'off'`` the remaining in-core
    choice (wavefront vs rowscan) comes from the cost-model ranking (or a
    tuning-table hit) instead of the legacy ``M < 2N`` rule."""
    if mesh is not None:
        return ("sharded", "structural",
                "mesh shards the reference axis", ())
    if top_k is not None:
        # The top-K heap rides the chunk boundary carry — streaming path.
        return ("chunked", "structural",
                "top-K heap rides the chunk boundary carry", ())
    if chunk is not None:
        return ("chunked", "structural",
                "explicit chunk forces streaming", ())
    backend = jax.default_backend() if backend is None else backend
    if backend == "tpu" and not has_exclusion:
        # The Pallas kernel streams arbitrary M through its own tile grid —
        # long references stay on the kernel path on the target hardware.
        return ("pallas", "structural",
                "TPU backend (kernel grid streams any M)", ())
    if m >= CHUNK_THRESHOLD:
        return ("chunked", "structural",
                f"M >= CHUNK_THRESHOLD (1<<{CHUNK_THRESHOLD.bit_length() - 1})",
                ())
    if tune != "off":
        from repro.tune import rank_incore
        res = rank_incore(nq, n, m, backend=backend, metric=metric,
                          dtype=dtype, mode=tune)
        impl = res.config.impl
        if impl in ("rowscan", "wavefront"):
            return (impl, res.source,
                    f"in-core ranking ({res.source})", res.candidates)
    if m < 2 * n:
        return ("wavefront", "legacy",
                "M < 2N: diagonal depth is cheap", ())
    return ("rowscan", "legacy", "default in-core schedule", ())


def choose_impl(nq: int, n: int, m: int, *, backend: Optional[str] = None,
                mesh=None, chunk: Optional[int] = None,
                has_exclusion: bool = False,
                top_k: Optional[int] = None, tune: str = "off",
                metric: str = "abs_diff", dtype: str = "int32") -> str:
    """The ``impl="auto"`` dispatch rule (documented in the module docstring,
    exercised directly by the tests).  ``tune`` defaults to ``'off'``
    (the legacy heuristics) here; ``SdtwRequest`` defaults to
    ``'model'``."""
    return choose_impl_explained(
        nq, n, m, backend=backend, mesh=mesh, chunk=chunk,
        has_exclusion=has_exclusion, top_k=top_k, tune=tune,
        metric=metric, dtype=dtype)[0]


def _bucket_len(length: int) -> int:
    return max(MIN_BUCKET, 1 << max(0, int(length) - 1).bit_length())


def _is_ragged(queries) -> bool:
    if isinstance(queries, (list, tuple)):
        return True
    return False


def _normalize_excl(val, nq: int):
    if val is None:
        return jnp.full((nq,), -1, jnp.int32)
    arr = jnp.asarray(val, jnp.int32)
    if arr.ndim == 0:
        arr = jnp.full((nq,), arr, jnp.int32)
    return arr


#: Kept as module aliases — the canonical definitions live with the
#: shared validator in ``repro.core.request``.
_resolve_mesh = resolve_mesh


def sdtw(queries, reference, qlens=None, *, metric: str = "abs_diff",
         impl: str = "auto", chunk: Optional[int] = None,
         excl_lo=None, excl_hi=None, mesh=None, mesh_shape=None,
         ref_axis: str = "ref", n_micro: Optional[int] = None,
         top_k: Optional[int] = None, return_positions: bool = False,
         return_spans: bool = False, excl_zone: Optional[int] = None,
         excl_mode: str = "end", block_q: Optional[int] = None,
         block_m: Optional[int] = None, tune: str = "model",
         explain: bool = False):
    """Subsequence-DTW distances of ``queries`` against ``reference``.

    Args:
      queries:   (nq, N) padded array, a single (N,) query, or a list of
                 1-D queries with mixed lengths (ragged — bucketed dispatch).
      reference: (M,) reference sequence.
      qlens:     (nq,) true query lengths for padded 2-D input.
      metric:    'abs_diff' | 'square_diff'.
      impl:      one of ``IMPLS``; 'auto' applies the dispatch rules above.
                 A forced impl rejects arguments belonging to another path.
      chunk:     reference tile size for the chunked/sharded paths (forces
                 streaming under 'auto'); with ``impl='pallas'`` the
                 reference is streamed through the kernel in chunk-sized
                 slices via the kernel carry.
      excl_lo/excl_hi: banned reference column range per query (self-join
                 exclusion zones); scalar or (nq,).
      mesh:      a jax Mesh whose ``ref_axis`` shards the reference axis;
                 forces the sharded driver under 'auto'. A 2-D (dp, mp)
                 mesh (see ``repro.distributed.get_mesh``) additionally
                 shards query microbatches over the dp rows.
      mesh_shape: build the mesh for you — an int, ``(mp,)`` or
                 ``(dp, mp)`` tuple (``-1`` wildcards allowed) passed to
                 ``repro.distributed.get_mesh``; mutually exclusive with
                 ``mesh``.
      n_micro:   microbatch count per dp row for the sharded systolic
                 schedule (default fills the pipeline); results are
                 bitwise-invariant to it for int32 inputs.
      top_k:     return the k best match end positions per query as
                 ``(dists (nq, k), positions (nq, k))``, best first,
                 suppressed so positions are > ``excl_zone`` apart.
      return_positions: return ``(dists, end_positions)`` (top-1); without
                 ``top_k`` this works on every impl.
      return_spans: return ``(dists, starts, ends)`` — the start-pointer
                 lane; works on every impl, stacks to (nq, k) with top_k.
      excl_zone: top-K suppression radius — semantics documented ONCE on
                 ``repro.core.request`` (shared with ``search_topk``):
                 ``None`` derives per query (half the true length, or 0
                 with ``excl_mode='span'``); scalar applies to all;
                 per-query (nq,) arrays run on the single-device chunked
                 path only.
      excl_mode: 'end' suppresses matches whose *end* is within
                 ``excl_zone``; 'span' suppresses matches whose spans
                 overlap (widened by ``excl_zone``). Only meaningful with
                 ``top_k``.
      block_q/block_m: Pallas kernel block shape (``None`` = auto-tuned
                 per backend; see ``repro.kernels.sdtw.resolve_blocks``).
      tune:      ``'model'`` (default) fills unset performance knobs —
                 in-core impl choice, kernel blocks, chunk size, sharded
                 microbatch count — from the ``repro.tune`` oracle (table
                 hit, else analytical cost model); ``'measure'``
                 additionally refines this bucket with a short on-device
                 measured search *before* dispatch (once per process per
                 bucket); ``'off'`` keeps the legacy hand-tuned
                 constants.  Explicit kwargs always win, and every tuned
                 knob is bitwise-safe: int32 results are invariant to it.
      explain:   return ``(result, decision)`` where ``decision`` is the
                 ``repro.tune.DispatchDecision`` describing which impl
                 and knobs ran and why (not supported for ragged lists —
                 buckets may dispatch differently).

    Returns: (nq,) distances in the accumulator dtype — scalar for a single
    1-D query; a (dists, positions) pair or (dists, starts, ends) triple
    in the positions/spans modes.
    """
    return SdtwRequest(
        queries=queries, reference=reference, qlens=qlens, metric=metric,
        impl=impl, chunk=chunk, excl_lo=excl_lo, excl_hi=excl_hi,
        mesh=mesh, mesh_shape=mesh_shape, ref_axis=ref_axis,
        n_micro=n_micro, top_k=top_k, return_positions=return_positions,
        return_spans=return_spans, excl_zone=excl_zone,
        excl_mode=excl_mode, block_q=block_q, block_m=block_m,
        tune=tune, explain=explain, op="sdtw").run()


def _execute_sdtw(req: SdtwRequest):
    """The engine dispatcher behind ``SdtwRequest.run()`` — the request is
    already validated/normalized (mesh resolved); this owns shape
    resolution, ``impl='auto'`` dispatch, and the execution paths."""
    (queries, reference, qlens, metric, impl, chunk, excl_lo, excl_hi,
     mesh, ref_axis, n_micro, top_k, return_positions, return_spans,
     excl_zone, excl_mode, block_q, block_m, tune, explain) = (
        req.queries, req.reference, req.qlens, req.metric, req.impl,
        req.chunk, req.excl_lo, req.excl_hi, req.mesh, req.ref_axis,
        req.n_micro, req.top_k, req.return_positions, req.return_spans,
        req.excl_zone, req.excl_mode, req.block_q, req.block_m,
        req.tune, req.explain)

    if _is_ragged(queries):
        if explain:
            raise ValueError(
                "explain=True is not supported for ragged query lists — "
                "each bucket may dispatch differently; call per bucket")
        with TraceAnnotation("engine.ragged"):
            return _sdtw_ragged(
                queries, reference, metric=metric, impl=impl, chunk=chunk,
                excl_lo=excl_lo, excl_hi=excl_hi, mesh=mesh,
                ref_axis=ref_axis, n_micro=n_micro, top_k=top_k,
                return_positions=return_positions,
                return_spans=return_spans, excl_zone=excl_zone,
                excl_mode=excl_mode, block_q=block_q, block_m=block_m,
                tune=tune)

    # Host work up to the backend call (the query's host-to-device copy,
    # the dispatch decision, tuning lookups) is ``engine.prepare``; the
    # call itself, which returns arrays the device has yet to compute,
    # is ``engine.launch``.
    with TraceAnnotation("engine.prepare"):
        queries = jnp.asarray(queries)
        reference = jnp.asarray(reference)
        single = queries.ndim == 1
        if single:
            queries = queries[None, :]
        nq, n = queries.shape
        m = reference.shape[0]
        if qlens is not None:
            qlens = jnp.asarray(qlens, jnp.int32)
        dtype = str(jnp.result_type(queries, reference))

        if tune == "measure":
            # Measured refinement must never run inside a trace — resolve
            # the bucket eagerly here (once per process per bucket; the LRU
            # and the process table absorb repeats), then every downstream
            # consultation is a table hit.
            from repro.tune import resolve as _tune_resolve
            _tune_resolve(nq, n, m, metric=metric, dtype=dtype,
                          mode="measure", span=return_spans)

        has_excl = excl_lo is not None or excl_hi is not None
        if impl == "auto":
            impl, source, reason, candidates = choose_impl_explained(
                nq, n, m, mesh=mesh, chunk=chunk, has_exclusion=has_excl,
                top_k=top_k, tune=tune, metric=metric, dtype=dtype)
        else:
            source, reason, candidates = (
                "explicit", "impl forced by the caller", ())
        if impl == "pallas" and has_excl:
            raise ValueError("the pallas kernel does not support exclusion "
                             "zones; use impl='rowscan' or 'chunked'")

        config: dict = {}
        if impl in ("rowscan", "wavefront"):
            lo = _normalize_excl(excl_lo, nq) if has_excl else None
            hi = _normalize_excl(excl_hi, nq) if has_excl else None
            launch = functools.partial(
                sdtw_batch, queries, reference, qlens, metric, impl, lo, hi,
                return_positions=return_positions, return_spans=return_spans)
        elif impl == "pallas":
            from repro.kernels.sdtw import (interpret_mode, resolve_blocks,
                                            sdtw_pallas)
            if explain:
                # The blocks of the launch below: a reference past
                # ``PALLAS_FUSED_MAX`` launches one chunk at a time.
                m_launch = (chunk if chunk is not None
                            and m > PALLAS_FUSED_MAX else m)
                rbq, rbm, rscheme, rrt = resolve_blocks(
                    nq, m_launch, block_q, block_m, None, None,
                    interpret_mode(), n=n, metric=metric, dtype=dtype,
                    tune=tune, span=return_spans)
                config = {"block_q": rbq, "block_m": rbm,
                          "scan_scheme": rscheme, "row_tile": rrt}
            if chunk is None:
                launch = functools.partial(
                    sdtw_pallas, queries, reference, qlens, metric,
                    block_q=block_q, block_m=block_m,
                    return_positions=return_positions,
                    return_spans=return_spans, tune=tune)
            else:
                launch = functools.partial(
                    _pallas_streamed, queries, reference, qlens, metric,
                    chunk, block_q, block_m, return_positions, return_spans,
                    tune=tune)
        elif impl == "chunked":
            if chunk is None and tune != "off":
                from repro.tune import tuned_chunk
                chunk = tuned_chunk(nq, n, m, metric=metric, dtype=dtype,
                                    mode=tune)
            config = {"chunk": chunk or DEFAULT_CHUNK}
            launch = functools.partial(
                sdtw_chunked, queries, reference, qlens, metric,
                chunk or DEFAULT_CHUNK, _normalize_excl(excl_lo, nq),
                _normalize_excl(excl_hi, nq), top_k=top_k,
                excl_zone=excl_zone, return_positions=return_positions,
                return_spans=return_spans, excl_mode=excl_mode)
        else:  # sharded
            from repro.distributed.sdtw_sharded import sdtw_sharded
            if n_micro is None and tune != "off" and mesh is not None:
                from repro.tune import resolve_n_micro
                sizes = dict(mesh.shape)
                n_mp = int(sizes.pop(ref_axis, 1))
                n_dp = int(np.prod(list(sizes.values()))) if sizes else 1
                n_micro = resolve_n_micro(nq, n_dp, n_mp, n=n, m=m,
                                          metric=metric, dtype=dtype,
                                          mode=tune)
            config = {"chunk": chunk or DEFAULT_CHUNK, "n_micro": n_micro}
            launch = functools.partial(
                sdtw_sharded, queries, reference, qlens, metric=metric,
                mesh=mesh, axis=ref_axis, n_micro=n_micro,
                chunk=chunk or DEFAULT_CHUNK,
                excl_lo=_normalize_excl(excl_lo, nq),
                excl_hi=_normalize_excl(excl_hi, nq),
                top_k=top_k, excl_zone=excl_zone,
                return_positions=return_positions,
                return_spans=return_spans, excl_mode=excl_mode)
    with TraceAnnotation("engine.launch", impl=impl, nq=nq):
        out = launch()
        if single:
            out = (tuple(o[0] for o in out) if isinstance(out, tuple)
                   else out[0])
    if explain:
        from repro.tune import DispatchDecision
        score = candidates[0][1] if candidates else None
        return out, DispatchDecision(impl=impl, source=source,
                                     reason=reason, config=config,
                                     score_us=score, candidates=candidates)
    return out


def stream(queries, *, qlens=None, metric: str = "abs_diff",
           impl: str = "auto", chunk: Optional[int] = None,
           mesh=None, mesh_shape=None,
           ref_axis: str = "ref", n_micro: Optional[int] = None,
           top_k: Optional[int] = None, excl_zone=None,
           excl_mode: str = "end", return_spans: bool = False,
           return_positions: bool = False, excl_lo=None, excl_hi=None,
           prune: bool = False, span_cap: Optional[int] = None,
           alert_threshold=None, on_alert=None, cache=None, ref_key=None,
           block_q: Optional[int] = None, block_m: Optional[int] = None,
           streams: int = 1):
    """Open an online monitoring session: the streaming front door.

    Where ``sdtw()`` answers one offline query batch against a
    materialized reference, ``stream()`` returns a session whose
    ``feed(chunk)`` consumes the reference as an unbounded chunk sequence
    — the chunk-carry protocol run forever. ``session.results()`` at any
    point equals the offline ``sdtw()`` / ``search_topk()`` answer over
    the samples fed so far (bitwise for int32, any feed partition);
    ``session.snapshot()`` / ``StreamSession.restore()`` give
    fault-tolerant serving. See ``repro.stream`` for the session API
    (top-K heaps, online LB pruning, threshold alerts).

    Dispatch: ``mesh=`` (or ``impl='sharded'``) returns the
    ``ShardedStreamSession`` (per-device chunk streams through the
    ppermute carry); ``impl='pallas'`` streams fed chunks through the
    kernel's carry entry/exit — including top-K heaps, threshold alerts
    and online pruning, which score on the kernel's in-kernel last-row
    capture; ``'auto'`` picks the Pallas path on a TPU backend (rowscan
    only for per-query exclusion zones, which the kernel does not
    support) and the rowscan tile loop everywhere else. ``chunk`` is the
    internal DP tile size (compile granularity) — feed granularity is
    independent of it. ``streams=S`` opens one session over S aligned
    streams that share the queries: ``feed`` takes ``(S, samples)`` and
    one launch per tile advances them all (not with ``mesh=``,
    ``prune=True`` or exclusion bands).
    """
    return StreamRequest(
        queries=queries, qlens=qlens, metric=metric, impl=impl,
        chunk=chunk, mesh=mesh, mesh_shape=mesh_shape, ref_axis=ref_axis,
        n_micro=n_micro, top_k=top_k, excl_zone=excl_zone,
        excl_mode=excl_mode, return_spans=return_spans,
        return_positions=return_positions, excl_lo=excl_lo,
        excl_hi=excl_hi, prune=prune, span_cap=span_cap,
        alert_threshold=alert_threshold, on_alert=on_alert, cache=cache,
        ref_key=ref_key, block_q=block_q, block_m=block_m,
        streams=streams).open()


def align(queries, reference, qlens=None, *, metric: str = "abs_diff",
          impl: str = "auto", chunk: Optional[int] = None, mesh=None,
          ref_axis: str = "ref",
          trace_chunk: int = DEFAULT_TRACE_CHUNK):
    """Best alignment of each query, localized: span plus full warping path.

    Composes two bounded-memory passes: (1) the engine's span mode finds
    ``(distance, start, end)`` on whatever execution path ``impl``/"auto"
    selects; (2) ``repro.core.traceback`` re-runs the DP inside the
    ``[start, end]`` window only, in ``trace_chunk``-column blocks, to
    recover the monotone warping path (peak memory
    O(N·trace_chunk + N·span/trace_chunk), never O(N·M)).

    Returns an ``AlignResult`` for a single 1-D query, else a list of
    ``AlignResult`` (one per query, in caller order; ragged lists
    accepted). Saturated matches (distance ≥ BIG — no finite alignment,
    e.g. fully banned reference) come back with ``start = end = -1`` and
    ``path = None``.
    """
    ragged = _is_ragged(queries)
    single = not ragged and jnp.asarray(queries).ndim == 1
    d, s, e = sdtw(queries, reference, qlens, metric=metric, impl=impl,
                   chunk=chunk, mesh=mesh, ref_axis=ref_axis,
                   return_spans=True)
    if single:
        d, s, e = d[None], s[None], e[None]
    d = np.asarray(d)
    s = np.asarray(s, np.int64)
    e = np.asarray(e, np.int64)
    if ragged:
        qs = [np.asarray(q) for q in queries]
        lens = [len(q) for q in qs]
    else:
        q2 = np.asarray(queries)
        q2 = q2[None, :] if q2.ndim == 1 else q2
        lens = (np.full((q2.shape[0],), q2.shape[1], np.int64)
                if qlens is None else np.asarray(qlens, np.int64))
        qs = [q2[i, :int(lens[i])] for i in range(q2.shape[0])]
    ref_np = np.asarray(reference)
    BIG = big(d.dtype)
    results = []
    for i, q in enumerate(qs):
        if d[i] >= BIG or s[i] < 0:
            results.append(AlignResult(distance=d[i], start=-1, end=-1,
                                       path=None))
            continue
        path = traceback_path(q, ref_np, int(s[i]), int(e[i]),
                              metric=metric, chunk=trace_chunk)
        results.append(AlignResult(distance=d[i], start=int(s[i]),
                                   end=int(e[i]), path=path))
    return results[0] if single else results


def _pallas_streamed(queries, reference, qlens, metric, chunk, block_q,
                     block_m, return_positions, return_spans=False,
                     tune: str = "off"):
    """The ``impl='pallas'`` + ``chunk=`` dispatcher.

    Device-resident references (M ≤ ``PALLAS_FUSED_MAX``) take the
    single-launch grid path — the kernel's own sequential tile dimension
    already streams the reference HBM→VMEM with the boundary column in
    VMEM scratch, so one compiled program covers the whole reference and
    ``chunk`` is advisory. Longer references run the device-side
    ``lax.scan`` over chunk-sized slices. Either way the carry never
    leaves the device and exactly one executable is compiled."""
    m = reference.shape[0]
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if m <= PALLAS_FUSED_MAX:
        from repro.kernels.sdtw import sdtw_pallas
        return sdtw_pallas(queries, reference, qlens, metric,
                           block_q=block_q, block_m=block_m,
                           return_positions=return_positions,
                           return_spans=return_spans, tune=tune)
    return _pallas_scan_streamed(queries, reference, qlens, metric,
                                 chunk=chunk, block_q=block_q,
                                 block_m=block_m,
                                 return_positions=return_positions,
                                 return_spans=return_spans, tune=tune)


def _unpack_pallas_carry(carry, return_positions, return_spans):
    if return_spans:
        _, _, best, pos, start = carry
        return best, start, pos
    _, best, pos = carry
    return (best, pos) if return_positions else best


@functools.partial(jax.jit, static_argnames=(
    "metric", "chunk", "block_q", "block_m", "return_positions",
    "return_spans", "tune"))
def _pallas_scan_streamed(queries, reference, qlens, metric, *, chunk,
                          block_q, block_m, return_positions,
                          return_spans, tune: str = "off"):
    """Device-side chunk pipeline: one jitted ``lax.scan`` over statically-
    shaped reference slices, chaining the kernel carry in device memory —
    no host hop between slices, one compile for any reference length (the
    ragged tail is right-padded to ``chunk`` and masked via the kernel's
    traced ``ref_len``). The start-pointer lane joins the carry only when
    spans are requested (the plain stream keeps the untaxed
    (bcol, best, pos) triple)."""
    from repro.kernels.sdtw import pallas_carry_init, sdtw_pallas
    b, n = queries.shape
    m = reference.shape[0]
    n_slices = -(-m // chunk)
    r_pad = jnp.pad(reference, (0, n_slices * chunk - m))
    slices = r_pad.reshape(n_slices, chunk)
    offs = jnp.arange(n_slices, dtype=jnp.int32) * chunk
    clens = jnp.minimum(chunk, m - offs)
    acc = accum_dtype(jnp.result_type(queries, reference))
    carry = pallas_carry_init(b, n, acc, track_start=return_spans)

    def step(c, xs):
        sl, off, cl = xs
        _, c2 = sdtw_pallas(queries, sl, qlens, metric, block_q=block_q,
                            block_m=block_m, carry=c, ref_offset=off,
                            ref_len=cl, return_carry=True,
                            track_start=return_spans, tune=tune)
        return c2, None

    carry, _ = jax.lax.scan(step, carry, (slices, offs, clens))
    return _unpack_pallas_carry(carry, return_positions, return_spans)


def _pallas_host_loop(queries, reference, qlens, metric, chunk, block_q=None,
                      block_m=None, return_positions=False,
                      return_spans=False):
    """Legacy host-side chunk loop: one kernel launch per slice, the carry
    round-tripping through dispatch. Not dispatched automatically (both
    device-side paths subsume it); kept as the semantic reference the
    device-side paths are differential-tested against, and for callers
    that need to slice a host-resident reference themselves.

    The ragged tail slice is right-padded to the static ``chunk`` shape
    and masked via the kernel's traced ``ref_len``, and the first slice
    starts from an explicit ``pallas_carry_init`` pytree, so the loop
    emits exactly one compiled executable for any reference length (the
    old version sliced ``reference[off:off + chunk]`` raw, recompiling for
    every distinct tail length)."""
    from repro.kernels.sdtw import pallas_carry_init, sdtw_pallas
    b, n = queries.shape
    m = reference.shape[0]
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    acc = accum_dtype(jnp.result_type(queries, reference))
    carry = pallas_carry_init(b, n, acc, track_start=return_spans)
    for off in range(0, m, chunk):
        sl = reference[off:off + chunk]
        cl = sl.shape[0]
        if cl < chunk:
            sl = jnp.pad(sl, (0, chunk - cl))
        _, carry = sdtw_pallas(queries, sl, qlens, metric, block_q=block_q,
                               block_m=block_m, carry=carry, ref_offset=off,
                               ref_len=cl, return_carry=True,
                               track_start=return_spans)
    return _unpack_pallas_carry(carry, return_positions, return_spans)


def bucketize(lengths: Sequence[int]):
    """Group query indices by padded power-of-two bucket length.

    Returns {bucket_len: [query indices]} with deterministic ordering.
    """
    buckets: dict[int, list[int]] = {}
    for i, L in enumerate(lengths):
        if L < 1:
            raise ValueError(f"query {i} is empty")
        buckets.setdefault(_bucket_len(L), []).append(i)
    return dict(sorted(buckets.items()))


def pad_ragged_bucket(qs, idxs, blen: int):
    """Materialise one ragged bucket: zero-pad the selected queries to
    (len(idxs), blen) in their promoted dtype.

    Shared by the engine's ragged dispatch and ``repro.search`` so the
    pad/bucket conventions cannot drift. Returns numpy
    ``(padded, qlens)``.
    """
    dtype = np.result_type(*[qs[i].dtype for i in idxs])
    padded = np.zeros((len(idxs), blen), dtype)
    qlens = np.empty((len(idxs),), np.int32)
    for k, i in enumerate(idxs):
        padded[k, :len(qs[i])] = qs[i]
        qlens[k] = len(qs[i])
    return padded, qlens


def _sdtw_ragged(queries, reference, *, metric, impl, chunk, excl_lo,
                 excl_hi, mesh, ref_axis, n_micro=None, top_k,
                 return_positions, return_spans, excl_zone, excl_mode,
                 block_q, block_m, tune: str = "model"):
    """Bucketed dispatch for mixed-length query sets."""
    qs = [np.asarray(q) for q in queries]
    nq = len(qs)
    n_out = (3 if return_spans
             else 2 if (top_k is not None or return_positions) else 1)
    if nq == 0:
        kk = 1 if top_k is None else top_k
        shape = (0,) if top_k is None else (0, kk)
        empty = tuple(jnp.zeros(shape, jnp.int32) for _ in range(n_out))
        return empty if n_out > 1 else empty[0]
    lo = np.asarray(_normalize_excl(excl_lo, nq))
    hi = np.asarray(_normalize_excl(excl_hi, nq))
    buckets = bucketize([len(q) for q in qs])

    outs = [[None] * nq for _ in range(n_out)]
    for blen, idxs in buckets.items():
        padded, qlens = pad_ragged_bucket(qs, idxs, blen)
        res = sdtw(jnp.asarray(padded), reference, jnp.asarray(qlens),
                   metric=metric, impl=impl, chunk=chunk,
                   excl_lo=jnp.asarray(lo[idxs]),
                   excl_hi=jnp.asarray(hi[idxs]),
                   mesh=mesh, ref_axis=ref_axis, n_micro=n_micro,
                   top_k=top_k,
                   return_positions=return_positions,
                   return_spans=return_spans, excl_zone=excl_zone,
                   excl_mode=excl_mode, block_q=block_q, block_m=block_m,
                   tune=tune)
        res = res if isinstance(res, tuple) else (res,)
        for t in range(n_out):
            for k, i in enumerate(idxs):
                outs[t][i] = res[t][k]
    stacked = tuple(jnp.stack(o) for o in outs)
    return stacked if n_out > 1 else stacked[0]
