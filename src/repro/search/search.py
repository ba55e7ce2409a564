"""Batched top-K pruned subsequence search — the query-answering front door.

``engine.sdtw()`` computes "how far is this query from its best alignment";
``search_topk()`` answers the paper's actual question (§I, §V): *where are
the K best matches of each query in this reference, and are they distinct
events?* It composes, in order:

  1. ragged-query bucketing (reused from the engine),
  2. optional z-normalization (global reference, per-query moments),
  3. the lower-bound cascade of ``repro.search.lower_bounds`` over the
     cached per-chunk envelope (``repro.search.cache``),
  4. chunk-level pruning: a reference chunk is dispatched to the DP only if
     some query's bound says it could still improve that query's heap,
  5. exact chunked DP with the top-K heap riding the boundary carry
     (``repro.core.sdtw.sdtw_segment_topk``), warmed up by a ``halo`` of
     left-context chunks so pruning never truncates an alignment.

Pruning semantics — two deviations from the exact streamed path:

  * **Span cap**: a match whose alignment path covers more than
    ``span_cap`` reference columns (default 2N; raise it or pass
    ``prune=False`` to lift) may be missed or scored from truncated
    context — reported *starts* inherit the same bound (a start earlier
    than the halo window cannot be observed). Under the cap, the top-1
    *distance* is exactly ``engine.sdtw()``'s answer (bitwise for int32).
    This caveat covers **profile mode** too: a pruned
    ``repro.search.profile.matrix_profile`` runs every window batch
    through this path, so a nearest neighbor aligned over more than
    ``span_cap`` (default ``2 * window``) columns may be missed there —
    ``matrix_profile(prune=False)`` (and the streaming
    ``StreamProfile``, which is always exact) lift it.
  * **Greedy order**: surviving chunks are visited in bound order, not
    reference order, so for k > 1 the exclusion-zone suppression can
    resolve differently than the streamed path — the reported set beyond
    top-1 is a best-effort greedy set (every entry is still a genuine
    alignment distance at a genuine end position, and an equally good or
    better pick at each greedy step), and exact distance ties can report
    a different (equally optimal) end position.

With ``prune=False`` the call lowers straight onto the engine's streaming
top-K path and both caveats vanish. Chunks are pruned only when *no*
query in the batch can improve — the batch shares the DP dispatch, as in
the engine.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.distances import accum_dtype
from repro.core.request import SdtwRequest
from repro.core.sdtw import (default_excl_zone, sdtw_carry_init,
                             sdtw_chunk_batch_topk, sdtw_segment,
                             topk_fold_lastrow)
from repro.core.topk import topk_init

from . import cache as cache_mod
from .lower_bounds import lb_cascade, znorm, znorm_padded

#: Default warping-span cap, in query lengths.
DEFAULT_SPAN_FACTOR = 2

#: Smallest pruning tile — below this the per-chunk dispatch overhead
#: exceeds the DP it would skip.
MIN_CHUNK = 64


@dataclasses.dataclass
class SearchResult:
    """Top-K matches plus pruning telemetry for one ``search_topk`` call."""
    distances: object           # (nq, k) best-first; BIG-padded
    positions: object           # (nq, k) global end indices; -1-padded
    chunk: int                  # pruning tile size used
    starts: object = None       # (nq, k) global start indices; -1-padded
    chunks_total: int = 0      # candidate chunks across all buckets
    chunks_pruned_kim: int = 0    # skipped on the constant-time bound
    chunks_pruned_keogh: int = 0  # skipped on the envelope bound
    chunks_processed: int = 0     # dispatched to the DP
    engine_impl: str = ""         # what scored the chunks: 'pallas' |
                                  # 'rowscan' ('chunked' / 'sharded' when
                                  # prune=False / mesh= stream instead)

    @property
    def chunks_pruned(self) -> int:
        return self.chunks_pruned_kim + self.chunks_pruned_keogh

    @property
    def spans(self):
        """(nq, k, 2) stacked (start, end) spans."""
        return jnp.stack([self.starts, self.positions], axis=-1)


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def default_chunk(m: int, n: int) -> int:
    """Pruning tile heuristic: ≥ MIN_CHUNK, ≥ the query (so one chunk can
    hold a whole match), ~eighth of the reference (so there is something
    to prune), capped at the engine's streaming default."""
    return max(MIN_CHUNK,
               min(engine.DEFAULT_CHUNK,
                   _pow2_at_least(max(n, m // 8))))


@functools.partial(jax.jit, static_argnames=("metric", "chunk", "halo", "k",
                                             "excl_span", "engine_impl"))
def _pruned_chunk_step(queries, qlens, seg, heap_d, heap_p, heap_s, j0,
                       m_total, excl_lo, excl_hi, excl_zone, *, metric,
                       chunk, halo, k, excl_span,
                       engine_impl: str = "rowscan"):
    """Score one surviving chunk and fold its candidates into the heap.

    ``seg`` is the chunk plus ``halo`` left-context chunks; the DP runs
    from a fresh carry at the group start (columns before the reference,
    j < 0, are masked), and only the *target* chunk's last-row candidates
    are harvested — the halo exists purely to warm the boundary carry
    (value *and* start-pointer lanes, so candidate spans beginning inside
    the halo are exact) so any match with span ≤ halo·chunk is scored
    with full context.

    ``engine_impl='pallas'`` scores the whole halo group in one kernel
    call using the in-kernel last-row capture (the group's leading pad /
    trailing overhang are masked via the kernel's traced ``ref_lead`` /
    ``ref_len`` window) and folds the identical candidate row with the
    identical per-chunk ``topk_merge`` — int32 heaps are bitwise-equal to
    the rowscan variant. Requires no per-query exclusion zones (the
    caller checks).
    """
    nq, n = queries.shape
    acc = accum_dtype(jnp.result_type(queries, seg))
    if engine_impl == "pallas":
        from repro.kernels.sdtw import sdtw_pallas
        seg_len = seg.shape[0]
        _, lrow, lstart = sdtw_pallas(
            queries, seg, qlens, metric, track_start=True,
            return_lastrow=True, ref_offset=j0,
            ref_len=jnp.clip(m_total - j0, 0, seg_len),
            ref_lead=jnp.maximum(0, -j0))
        return topk_fold_lastrow(
            (heap_d.astype(acc), heap_p, heap_s),
            lrow[:, halo * chunk:], lstart[:, halo * chunk:],
            j0 + halo * chunk, k, excl_zone, excl_span)
    carry = sdtw_carry_init(nq, n, acc, track_start=True)
    if halo:
        carry = sdtw_segment(queries, seg[:halo * chunk], qlens, carry, j0,
                             m_total, metric, chunk, excl_lo, excl_hi)
    carry = carry + (heap_d.astype(acc), heap_p, heap_s)
    _, _, _, heap_d, heap_p, heap_s = sdtw_chunk_batch_topk(
        queries, seg[halo * chunk:], qlens, carry, j0 + halo * chunk,
        m_total, metric, excl_lo, excl_hi, k, excl_zone,   # (nq,) zone
        excl_span, track_start=True)
    return heap_d, heap_p, heap_s


def _search_padded(queries, reference, qlens, *, k, metric, chunk, prune,
                   halo, excl_zone, excl_mode, excl_lo, excl_hi, env,
                   engine_impl="rowscan"):
    """Pruned search for one padded (nq, N) bucket. Returns
    (dists, positions, starts, stats_tuple)."""
    nq, n = queries.shape
    m = reference.shape[0]
    acc = accum_dtype(jnp.result_type(queries, reference))
    n_chunks = -(-m // chunk)

    if not prune:
        d, s, p = engine.sdtw(queries, reference, qlens, metric=metric,
                              impl="chunked", chunk=chunk, top_k=k,
                              excl_zone=excl_zone, excl_lo=excl_lo,
                              excl_hi=excl_hi, excl_mode=excl_mode,
                              return_spans=True)
        return d, p, s, (n_chunks, 0, 0, n_chunks)

    if qlens is None:
        qlens = jnp.full((nq,), n, jnp.int32)
    excl_lo = jnp.asarray(engine._normalize_excl(excl_lo, nq))
    excl_hi = jnp.asarray(engine._normalize_excl(excl_hi, nq))
    if excl_zone is None:
        zone = (default_excl_zone(qlens) if excl_mode == "end"
                else jnp.zeros((nq,), jnp.int32))
    else:
        zone = jnp.full((nq,), int(excl_zone), jnp.int32)

    mins, maxs = env
    kim, keogh = lb_cascade(queries, qlens, mins, maxs, halo, metric)
    kim = np.asarray(kim)
    keogh = np.asarray(keogh)

    # Right-pad to a chunk multiple, left-pad a halo of masked columns so
    # every chunk group has the same static shape (j < 0 is banned in the
    # DP's global-position mask).
    r_pad = jnp.pad(reference, (0, n_chunks * chunk - m))
    r_ext = jnp.pad(r_pad, (halo * chunk, 0))

    heap_d, heap_p, heap_s = topk_init(nq, k, acc)
    pruned_kim = pruned_keogh = processed = 0
    # Most promising chunks first: thresholds tighten fastest, later
    # chunks die on the cheap bound. The k-th-best threshold only moves
    # when a chunk is actually processed, so the device→host fetch
    # happens per *processed* chunk, not per candidate.
    thr = np.asarray(heap_d[:, -1], np.float64)
    order = np.argsort(keogh.min(axis=0), kind="stable")
    for c in order:
        if np.all(kim[:, c] >= thr):
            pruned_kim += 1
            continue
        if np.all(keogh[:, c] >= thr):
            pruned_keogh += 1
            continue
        processed += 1
        group = r_ext[c * chunk:(c + halo + 1) * chunk]  # static shape ∀ c
        heap_d, heap_p, heap_s = _pruned_chunk_step(
            queries, qlens, group, heap_d, heap_p, heap_s,
            jnp.int32((c - halo) * chunk), jnp.int32(m), excl_lo, excl_hi,
            zone, metric=metric, chunk=chunk, halo=halo, k=k,
            excl_span=(excl_mode == "span"), engine_impl=engine_impl)
        thr = np.asarray(heap_d[:, -1], np.float64)
    return heap_d, heap_p, heap_s, (n_chunks, pruned_kim, pruned_keogh,
                                    processed)


def search_topk(queries, reference, k: int = 1, *, qlens=None,
                metric: str = "abs_diff", chunk: Optional[int] = None,
                prune: bool = True, span_cap: Optional[int] = None,
                excl_zone: Optional[int] = None, excl_mode: str = "end",
                normalize: bool = False,
                excl_lo=None, excl_hi=None, mesh=None, ref_axis: str = "ref",
                cache: Optional[cache_mod.EnvelopeCache] = None,
                ref_key=None, engine_impl: str = "auto") -> SearchResult:
    """Top-K subsequence matches of each query in ``reference``.

    Args:
      queries:   (nq, N) padded array, one (N,) query, or a ragged list.
      reference: (M,) reference sequence.
      k:         matches per query.
      qlens:     true lengths for padded 2-D input.
      metric:    'abs_diff' | 'square_diff'.
      chunk:     pruning tile size (default: ``default_chunk``).
      prune:     apply the LB cascade; ``False`` = exact engine streaming.
      span_cap:  max alignment span (columns) the pruned path scores with
                 full context; default ``2 * N`` (the same cap bounds a
                 pruned ``matrix_profile``'s nearest neighbors — see the
                 module docstring).
      excl_zone: suppression radius between reported matches (default:
                 half of each query's true length — or 0 with
                 ``excl_mode='span'``).
      excl_mode: 'end' suppresses matches whose ends are within
                 ``excl_zone`` (matrix-profile convention); 'span'
                 suppresses matches whose ``[start, end]`` spans overlap
                 (widened by ``excl_zone``) — reported events share no
                 reference samples.
      normalize: z-normalize reference (globally) and queries (per true
                 length) first; output distances are then in z-space.
      excl_lo/excl_hi: banned reference column range per query.
      mesh:      shard the reference axis instead of pruning (the sharded
                 engine streams every chunk; the cascade is host-side and
                 single-process, so mesh and prune are mutually exclusive).
      cache:     ``EnvelopeCache`` for the per-reference envelope
                 (default: the module-level ``DEFAULT_CACHE``).
      ref_key:   stable cache key for the reference (recommended).
      engine_impl: DP backend for scoring surviving chunks: 'rowscan'
                 (the chunked tile loop) or 'pallas' (the kernel's
                 in-kernel last-row capture — int32 heaps bitwise-equal
                 to rowscan). 'auto' picks pallas on a TPU backend when
                 no per-query exclusion zones are set.

    Returns a ``SearchResult``; distances/positions/starts are (nq, k)
    (or (k,) for a single 1-D query), best first, ``(BIG, -1, -1)``-padded
    when fewer than k sufficiently-distinct matches exist. ``starts`` is
    the DP start-pointer lane: the row-0 reference column where each
    match's alignment begins, so ``(starts[i, j], positions[i, j])`` is
    the j-th best matched span of query i.

    ``excl_zone`` semantics (incl. the per-query default) are documented
    once on ``repro.core.request`` — this front door is a thin shim over
    ``SdtwRequest(op='search_topk')``.
    """
    return SdtwRequest(
        op="search_topk", queries=queries, reference=reference, top_k=k,
        qlens=qlens, metric=metric, chunk=chunk, prune=prune,
        span_cap=span_cap, excl_zone=excl_zone, excl_mode=excl_mode,
        normalize=normalize, excl_lo=excl_lo, excl_hi=excl_hi, mesh=mesh,
        ref_axis=ref_axis, cache=cache, ref_key=ref_key,
        engine_impl=engine_impl).run()


def _execute_search(req: SdtwRequest) -> SearchResult:
    """The search dispatcher behind ``SdtwRequest.run()`` — the request
    is already validated/normalized."""
    (queries, reference, k, qlens, metric, chunk, prune, span_cap,
     excl_zone, excl_mode, normalize, excl_lo, excl_hi, mesh, ref_axis,
     cache, ref_key, engine_impl) = (
        req.queries, req.reference, req.top_k, req.qlens, req.metric,
        req.chunk, req.prune, req.span_cap, req.excl_zone, req.excl_mode,
        req.normalize, req.excl_lo, req.excl_hi, req.mesh, req.ref_axis,
        req.cache, req.ref_key, req.engine_impl)

    has_excl = excl_lo is not None or excl_hi is not None
    if engine_impl == "auto":
        engine_impl = ("pallas" if jax.default_backend() == "tpu"
                       and not has_excl else "rowscan")
    reference = jnp.asarray(reference)
    if normalize:
        reference = znorm(reference)
    m = reference.shape[0]
    cache = cache_mod.DEFAULT_CACHE if cache is None else cache

    ragged = isinstance(queries, (list, tuple))
    if ragged:
        qs = [np.asarray(q) for q in queries]
        buckets = engine.bucketize([len(q) for q in qs])
        nq = len(qs)
        lo_all = np.asarray(engine._normalize_excl(excl_lo, nq))
        hi_all = np.asarray(engine._normalize_excl(excl_hi, nq))
    else:
        queries = jnp.asarray(queries)
        single = queries.ndim == 1
        if single:
            queries = queries[None, :]
        nq = queries.shape[0]
        buckets = {queries.shape[1]: list(range(nq))}
        qs = None
        lo_all = hi_all = None

    out_d = [None] * nq
    out_p = [None] * nq
    out_s = [None] * nq
    totals = [0, 0, 0, 0]
    used_chunk = None
    for blen, idxs in buckets.items():
        if ragged:
            padded, lens = engine.pad_ragged_bucket(qs, idxs, blen)
            bq = jnp.asarray(padded)
            bql = jnp.asarray(lens)
            blo = jnp.asarray(lo_all[idxs])
            bhi = jnp.asarray(hi_all[idxs])
        else:
            bq, bql, blo, bhi = queries, qlens, excl_lo, excl_hi
        if normalize:
            bq = znorm_padded(
                bq, jnp.full((len(idxs),), blen, jnp.int32)
                if bql is None else bql)

        n = bq.shape[1]
        c = default_chunk(m, n) if chunk is None else int(chunk)
        used_chunk = c if used_chunk is None else max(used_chunk, c)
        cap = DEFAULT_SPAN_FACTOR * n if span_cap is None else int(span_cap)
        halo = max(1, -(-cap // c))

        if mesh is not None:
            d, s, p = engine.sdtw(bq, reference, bql, metric=metric,
                                  mesh=mesh, ref_axis=ref_axis, chunk=c,
                                  top_k=k, excl_zone=excl_zone,
                                  excl_mode=excl_mode, excl_lo=blo,
                                  excl_hi=bhi, return_spans=True)
            stats = (-(-m // c), 0, 0, -(-m // c))
        else:
            # The cached envelope belongs to the array actually searched —
            # a normalized search must not share entries with a raw one
            # under the same user key.
            env_key = (None if ref_key is None
                       else (ref_key, bool(normalize)))
            env = cache.envelope(reference, c, key=env_key) if prune \
                else None
            d, p, s, stats = _search_padded(
                bq, reference, bql, k=k, metric=metric, chunk=c,
                prune=prune, halo=halo, excl_zone=excl_zone,
                excl_mode=excl_mode, excl_lo=blo, excl_hi=bhi, env=env,
                engine_impl=engine_impl)
        for t in range(4):
            totals[t] += stats[t]
        d = np.asarray(d)
        p = np.asarray(p)
        s = np.asarray(s)
        for j, i in enumerate(idxs):
            out_d[i] = d[j]
            out_p[i] = p[j]
            out_s[i] = s[j]

    dists = jnp.asarray(np.stack(out_d))
    poss = jnp.asarray(np.stack(out_p))
    starts = jnp.asarray(np.stack(out_s))
    if not ragged and single:
        dists, poss, starts = dists[0], poss[0], starts[0]
    return SearchResult(distances=dists, positions=poss, starts=starts,
                        chunk=used_chunk,
                        engine_impl=("sharded" if mesh is not None
                                     else engine_impl if prune
                                     else "chunked"),
                        chunks_total=totals[0], chunks_pruned_kim=totals[1],
                        chunks_pruned_keogh=totals[2],
                        chunks_processed=totals[3])
