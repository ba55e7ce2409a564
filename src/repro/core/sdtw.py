"""Production sDTW implementations in JAX.

Two execution schemes, both using the paper's O(4M)-style linear memory
mapping (no N×M matrix is ever materialised):

``sdtw_wavefront``
    Paper-faithful anti-diagonal wavefront (MATSA §III-E): a scan over the
    N+M-1 anti-diagonals, vectorized along the diagonal. This mirrors MATSA's
    PE array, where each crossbar column is one wavefront element and data is
    shifted diagonally between steps. Sequential depth: N+M-1.

``sdtw_rowscan``
    Beyond-paper TPU-native scheme. The row recurrence

        s[j] = d[j] + min(m[j], s[j-1]),   m[j] = min(prev[j-1], prev[j])

    is a first-order *linear* recurrence over the (min, +) tropical semiring:
    with u[j] = d[j] + m[j] it is  s[j] = min(u[j], d[j] + s[j-1]), i.e. the
    tropical analogue of s_j = a_j * s_{j-1} + b_j. It therefore admits an
    associative-scan solution with O(log M) depth per row. Sequential depth:
    N (vs N+M-1) — a massive win when the reference is much longer than the
    query, which is the common case in the paper's workloads (e.g. ECG:
    M=1.8M, N=512). MATSA's bit-serial PEs cannot express this; TPU VPUs can.

Both return ``min(S[N-1, :])`` per Algorithm 1 and are validated against
the test oracle over shape/dtype/metric sweeps in the test suite.

Match spans (the start-pointer lane)
------------------------------------
Every scheme can additionally report *where* the best alignment begins:
each DP cell carries, alongside its value, the row-0 reference column
where its best path started. The combined lane is a lexicographic
``(value, start)`` pair — lower value wins, value ties take the smaller
start — which keeps the lane associative, so it rides the tropical
associative scan, the anti-diagonal shift, the chunk boundary-column
carry, and the sharded ``ppermute`` hand-off unchanged. Reported spans
are therefore deterministic and identical across every execution regime:
``start`` is the smallest row-0 column among all minimum-cost alignments
ending at the reported (leftmost-argmin) end column. Start values are
meaningless (and unspecified) when the distance saturates at BIG.

Exclusion zones (for self-join / matrix-profile-style use) are supported by
banning a column range [excl_lo, excl_hi): any path through those reference
positions is given +inf distance, which removes trivial self-matches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .distances import (INT_FAR as _INT_FAR_INT, accum_dtype, big, lex_min,
                        pointwise_distance, sat_add, tropical_combine,
                        tropical_combine_span)
from .topk import topk_init, topk_merge

#: See ``repro.core.distances.INT_FAR`` — re-bound as an int32 scalar for
#: the jnp lanes here.
INT_FAR = np.int32(_INT_FAR_INT)

_lex_min = lex_min

# The (min,+) semiring combines live in ``repro.core.distances`` (shared
# with the Pallas kernel's work-efficient scan scheme); the old private
# names stay bound for existing importers.
_tropical_combine = tropical_combine
_tropical_combine_span = tropical_combine_span


def _masked_distance(qi, ref, metric, excl_lo, excl_hi, BIG):
    d = pointwise_distance(qi, ref, metric)
    j = jnp.arange(ref.shape[0])
    banned = (j >= excl_lo) & (j < excl_hi)
    return jnp.where(banned, BIG, d)


# ---------------------------------------------------------------------------
# Row-scan (associative scan over the tropical semiring) — beyond-paper.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "return_position",
                                             "return_spans"))
def sdtw_rowscan(query, reference, qlen=None, metric: str = "abs_diff",
                 excl_lo=None, excl_hi=None, return_position: bool = False,
                 return_spans: bool = False):
    """sDTW distance via per-row tropical associative scan.

    Args:
      query:     (N,) possibly padded query.
      reference: (M,) reference sequence.
      qlen:      actual query length (<= N); defaults to N. Padded rows are
                 ignored — the answer is min over row ``qlen - 1``.
      metric:    'abs_diff' | 'square_diff'.
      excl_lo/excl_hi: optional banned reference column range (self-join).
      return_position: also return the match end position — the leftmost
                 reference index attaining the minimum of row ``qlen - 1``.
      return_spans: return ``(distance, start, end)`` — the start-pointer
                 lane rides the associative scan as a lexicographic
                 (value, start) pair.

    Returns: scalar sDTW distance in the accumulator dtype (or a
    ``(distance, end_position)`` pair with ``return_position=True``, or a
    ``(distance, start, end)`` triple with ``return_spans=True``).
    """
    acc = accum_dtype(jnp.result_type(query, reference))
    BIG = big(acc)
    n = query.shape[0]
    m = reference.shape[0]
    qlen = jnp.asarray(n if qlen is None else qlen, jnp.int32)
    excl_lo = jnp.asarray(-1 if excl_lo is None else excl_lo, jnp.int32)
    excl_hi = jnp.asarray(-1 if excl_hi is None else excl_hi, jnp.int32)

    d0 = _masked_distance(query[0], reference, metric, excl_lo, excl_hi, BIG)
    prev = d0                                           # row 0: free start
    best0 = jnp.where(qlen == 1, jnp.min(d0), BIG)
    pos0 = jnp.where(qlen == 1, jnp.argmin(d0).astype(jnp.int32),
                     jnp.int32(-1))

    if not return_spans:
        def row_step(carry, qi):
            prev, best, pos, i = carry
            d = _masked_distance(qi, reference, metric, excl_lo, excl_hi,
                                 BIG)
            prev_shift = jnp.concatenate([jnp.full((1,), BIG, acc),
                                          prev[:-1]])
            mn = jnp.minimum(prev_shift, prev)  # min(S[i-1,j-1], S[i-1,j])
            s0 = sat_add(prev[0], d[0])         # column-0 accumulation
            u = sat_add(d, mn).at[0].set(s0)
            a = d.at[0].set(BIG)
            _, s = lax.associative_scan(_tropical_combine, (a, u))
            hit = i == qlen - 1
            best = jnp.where(hit, jnp.minimum(best, jnp.min(s)), best)
            pos = jnp.where(hit, jnp.argmin(s).astype(jnp.int32), pos)
            # Freeze rows past the true query end so `prev` stays
            # meaningless-safe.
            return (s, best, pos, i + 1), None

        (_, best, pos, _), _ = lax.scan(
            row_step, (prev, best0, pos0, jnp.int32(1)), query[1:])
        if return_position:
            return best, pos
        return best

    # Span mode: the start lane rides every cell as a lex (value, start)
    # pair. Row 0 starts fresh at its own column.
    pstart0 = jnp.arange(m, dtype=jnp.int32)
    start0 = jnp.where(qlen == 1, pos0, jnp.int32(-1))

    def row_step_span(carry, qi):
        prev, pstart, best, pos, start, i = carry
        d = _masked_distance(qi, reference, metric, excl_lo, excl_hi, BIG)
        prev_shift = jnp.concatenate([jnp.full((1,), BIG, acc), prev[:-1]])
        pstart_shift = jnp.concatenate([jnp.full((1,), INT_FAR, jnp.int32),
                                        pstart[:-1]])
        mn, mns = _lex_min(prev_shift, pstart_shift, prev, pstart)
        s0 = sat_add(prev[0], d[0])             # column-0 accumulation
        u = sat_add(d, mn).at[0].set(s0)
        su = mns.at[0].set(pstart[0])
        a = d.at[0].set(BIG)
        _, s, sstart = lax.associative_scan(_tropical_combine_span,
                                            (a, u, su))
        hit = i == qlen - 1
        j = jnp.argmin(s).astype(jnp.int32)
        best = jnp.where(hit, jnp.minimum(best, jnp.min(s)), best)
        pos = jnp.where(hit, j, pos)
        start = jnp.where(hit, sstart[j], start)
        return (s, sstart, best, pos, start, i + 1), None

    (_, _, best, pos, start, _), _ = lax.scan(
        row_step_span, (prev, pstart0, best0, pos0, start0, jnp.int32(1)),
        query[1:])
    return best, start, pos


# ---------------------------------------------------------------------------
# Anti-diagonal wavefront — paper-faithful (MATSA §III-E execution flow).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "return_position",
                                             "return_spans"))
def sdtw_wavefront(query, reference, qlen=None, metric: str = "abs_diff",
                   excl_lo=None, excl_hi=None, return_position: bool = False,
                   return_spans: bool = False):
    """sDTW distance via anti-diagonal wavefront scan (MATSA's schedule).

    Diagonal k holds cells (i, j) with i + j = k, indexed by i. The carry is
    the last two diagonals (the paper's temporal S_vectors); each step
    consumes one new reference "column" — the direct analogue of MATSA's
    diagonal row copies between crossbar columns. With
    ``return_position=True`` the leftmost end index of the best match is
    tracked alongside (diagonal k touches row qlen-1 at exactly one column,
    ``k - qlen + 1``, and k ascends — a strict improvement test keeps the
    earliest column, matching ``sdtw_rowscan``'s leftmost ``argmin``).
    ``return_spans=True`` additionally shifts the start-pointer lane with
    the diagonals and returns ``(distance, start, end)``.
    """
    acc = accum_dtype(jnp.result_type(query, reference))
    BIG = big(acc)
    n = query.shape[0]
    m = reference.shape[0]
    qlen = jnp.asarray(n if qlen is None else qlen, jnp.int32)
    excl_lo = jnp.asarray(-1 if excl_lo is None else excl_lo, jnp.int32)
    excl_hi = jnp.asarray(-1 if excl_hi is None else excl_hi, jnp.int32)

    q = query.astype(accum_dtype(query.dtype))
    # R[k - i] for i in [0, n): pad front with n-1 dummies, slice, reverse.
    r_pad = jnp.concatenate([jnp.zeros((n - 1,), reference.dtype), reference,
                             jnp.zeros((n,), reference.dtype)])
    i_idx = jnp.arange(n)

    def cell_inputs(k):
        j_idx = k - i_idx                               # ref position per cell
        valid = (j_idx >= 0) & (j_idx < m) & (i_idx < qlen)
        r_rev = lax.dynamic_slice(r_pad, (k,), (n,))[::-1]
        d = pointwise_distance(q, r_rev.astype(acc), metric)
        banned = (j_idx >= excl_lo) & (j_idx < excl_hi)
        return j_idx, valid, jnp.where(banned, BIG, d)

    if not return_spans:
        def step(carry, k):
            dm1, dm2, best, pos = carry
            j_idx, valid, d = cell_inputs(k)
            shift1 = jnp.concatenate(
                [jnp.full((1,), BIG, acc), dm1[:-1]])   # S[i-1,j]
            shift2 = jnp.concatenate(
                [jnp.full((1,), BIG, acc), dm2[:-1]])   # S[i-1,j-1]
            mins = jnp.minimum(jnp.minimum(shift2, shift1), dm1)  # +S[i,j-1]
            cur = jnp.where(i_idx == 0, d, sat_add(d, mins))
            cur = jnp.where(valid, cur, BIG)
            last = jnp.where((i_idx == qlen - 1) & valid, cur, BIG)
            lmin = jnp.min(last)
            pos = jnp.where(lmin < best, (k - qlen + 1).astype(jnp.int32),
                            pos)
            best = jnp.minimum(best, lmin)
            return (cur, dm1, best, pos), None

        init = (jnp.full((n,), BIG, acc), jnp.full((n,), BIG, acc), BIG,
                jnp.int32(-1))
        (_, _, best, pos), _ = lax.scan(step, init, jnp.arange(n + m - 1))
        if return_position:
            return best, pos
        return best

    def step_span(carry, k):
        dm1, sm1, dm2, sm2, best, pos, start = carry
        j_idx, valid, d = cell_inputs(k)
        shift1v = jnp.concatenate([jnp.full((1,), BIG, acc), dm1[:-1]])
        shift1s = jnp.concatenate([jnp.full((1,), INT_FAR, jnp.int32),
                                   sm1[:-1]])
        shift2v = jnp.concatenate([jnp.full((1,), BIG, acc), dm2[:-1]])
        shift2s = jnp.concatenate([jnp.full((1,), INT_FAR, jnp.int32),
                                   sm2[:-1]])
        mv, ms = _lex_min(shift2v, shift2s, shift1v, shift1s)
        mv, ms = _lex_min(mv, ms, dm1, sm1)
        cur = jnp.where(i_idx == 0, d, sat_add(d, mv))
        curs = jnp.where(i_idx == 0, j_idx.astype(jnp.int32), ms)
        cur = jnp.where(valid, cur, BIG)
        curs = jnp.where(valid, curs, INT_FAR)
        at_last = (i_idx == qlen - 1) & valid
        last = jnp.where(at_last, cur, BIG)
        lmin = jnp.min(last)
        lstart = jnp.min(jnp.where(at_last, curs, INT_FAR))
        improve = lmin < best
        pos = jnp.where(improve, (k - qlen + 1).astype(jnp.int32), pos)
        start = jnp.where(improve, lstart, start)
        best = jnp.minimum(best, lmin)
        return (cur, curs, dm1, sm1, best, pos, start), None

    init = (jnp.full((n,), BIG, acc), jnp.full((n,), INT_FAR, jnp.int32),
            jnp.full((n,), BIG, acc), jnp.full((n,), INT_FAR, jnp.int32),
            BIG, jnp.int32(-1), jnp.int32(-1))
    (_, _, _, _, best, pos, start), _ = lax.scan(step_span, init,
                                                 jnp.arange(n + m - 1))
    return best, start, pos


# ---------------------------------------------------------------------------
# Chunked reference streaming (boundary-column carry).
#
# The reference axis is processed in fixed-size tiles; between tiles only the
# O(N) boundary column S[:, tile_end] is carried — the direct analogue of
# MATSA's inter-subarray pass gates (§III-B). The same carry doubles as the
# inter-device protocol of ``repro.distributed.sdtw_sharded`` (ppermute the
# column to the device holding the next reference segment). In span /
# top-K mode the carry gains the start-pointer lane: the boundary column
# becomes a (value, start) pair of lanes, and the heap holds
# (dist, end, start) triples.
# ---------------------------------------------------------------------------

def sdtw_carry_init(nq: int, n: int, acc, track_start: bool = False):
    """Fresh chunk carry: ``(boundary column (nq, N), running best (nq,))``,
    or ``(bcol, bstart, best)`` with ``track_start=True``.

    BIG everywhere = "no reference columns seen yet": a BIG left/diagonal
    neighbour reproduces the global column-0 recurrence exactly (the only
    finite predecessor of cell (i, 0) is S[i-1, 0]). The start lane is
    seeded with INT_FAR so the empty carry never wins a lexicographic
    tie."""
    BIG = big(acc)
    if track_start:
        return (jnp.full((nq, n), BIG, acc),
                jnp.full((nq, n), INT_FAR, jnp.int32),
                jnp.full((nq,), BIG, acc))
    return jnp.full((nq, n), BIG, acc), jnp.full((nq,), BIG, acc)


def _chunk_masked_distance(qi, ref_chunk, metric, j0, m_total, excl_lo,
                           excl_hi, BIG):
    """Distance row for one chunk, masking by *global* reference position.

    Columns outside [0, m_total) are banned — a negative ``j0`` lets the
    pruned-search halo pad a chunk group past the left edge of the
    reference without perturbing the DP (the pad columns behave exactly
    like the implicit BIG columns before the reference starts)."""
    d = pointwise_distance(qi, ref_chunk, metric)
    j = j0 + jnp.arange(ref_chunk.shape[0])
    banned = ((j >= excl_lo) & (j < excl_hi)) | (j >= m_total) | (j < 0)
    return jnp.where(banned, BIG, d)


def sdtw_rowscan_chunk(query, ref_chunk, bcol, best, qlen=None, j0=0,
                       m_total=None, metric: str = "abs_diff",
                       excl_lo=None, excl_hi=None,
                       return_lastrow: bool = False, bstart=None,
                       clen=None):
    """One reference chunk of the row-scan, entered/exited via the carry.

    Args:
      query:     (N,) possibly padded query.
      ref_chunk: (C,) reference tile covering global columns [j0, j0 + C).
      bcol:      (N,) boundary column S[:, j0 - 1] (BIG for the first chunk).
      best:      scalar running best (min over row qlen-1 of prior chunks).
      qlen:      true query length; j0: global column offset of the chunk;
      m_total:   true reference length (columns >= m_total are masked).
      return_lastrow: also return row ``qlen - 1`` of the chunk — the match
                 score of every alignment *ending* at each of the chunk's
                 columns, which is what top-K extraction consumes.
      bstart:    (N,) start lane of the boundary column (INT_FAR for the
                 first chunk). Passing it switches on start tracking: every
                 output gains the matching start lane.
      clen:      true number of reference columns in this chunk (traced;
                 defaults to C). With ``clen`` the returned boundary column
                 is S[:, j0 + clen - 1] instead of the final chunk column,
                 so a tile right-padded past the true stream end (the pad
                 columns must be banned via ``m_total``) still exits with a
                 carry the next chunk can continue from — the streaming
                 session's one-compiled-shape-per-tile trick.

    Returns ``(new_bcol, new_best)`` with new_bcol = S[:, j0 + C - 1] (or
    at ``clen - 1``), plus the (C,) last row when ``return_lastrow``. With
    ``bstart`` the returns become
    ``(new_bcol, new_bstart, new_best[, lastrow, lastrow_starts])``.
    """
    track = bstart is not None
    if clen is None:
        pick = lambda v: v[-1]
    else:
        _cl = jnp.asarray(clen, jnp.int32) - 1
        pick = lambda v: lax.dynamic_index_in_dim(v, _cl, keepdims=False)
    acc = accum_dtype(jnp.result_type(query, ref_chunk))
    BIG = big(acc)
    n = query.shape[0]
    qlen = jnp.asarray(n if qlen is None else qlen, jnp.int32)
    m_total = (j0 + ref_chunk.shape[0] if m_total is None else m_total)
    excl_lo = jnp.asarray(-1 if excl_lo is None else excl_lo, jnp.int32)
    excl_hi = jnp.asarray(-1 if excl_hi is None else excl_hi, jnp.int32)
    bcol = bcol.astype(acc)
    best = jnp.asarray(best, acc)

    dist = functools.partial(_chunk_masked_distance, metric=metric, j0=j0,
                             m_total=m_total, excl_lo=excl_lo,
                             excl_hi=excl_hi, BIG=BIG)
    s0 = dist(query[0], ref_chunk)                  # row 0: free start
    st0 = (j0 + jnp.arange(ref_chunk.shape[0])).astype(jnp.int32)
    best = jnp.where(qlen == 1, jnp.minimum(best, jnp.min(s0)), best)

    # The (C,) last-row buffer (and the start lane) ride the carry only
    # when asked for — the plain streaming hot path stays untaxed.
    def row_step(carry, xs):
        if track:
            if return_lastrow:
                prev, pstart, best, lrow, lstart, i = carry
            else:
                prev, pstart, best, i = carry
            qi, b_left, b_diag, bs_left, bs_diag = xs
        else:
            if return_lastrow:
                prev, best, lrow, i = carry
            else:
                prev, best, i = carry
            qi, b_left, b_diag = xs      # S[i, j0-1], S[i-1, j0-1]
        d = dist(qi, ref_chunk)
        prev_sh = jnp.concatenate([b_diag[None], prev[:-1]])
        if track:
            pstart_sh = jnp.concatenate([bs_diag[None], pstart[:-1]])
            mn, mns = _lex_min(prev_sh, pstart_sh, prev, pstart)
            a, u, su = d, sat_add(d, mn), mns
            a_p, u_p, su_p = lax.associative_scan(_tropical_combine_span,
                                                  (a, u, su))
            # Fold in S[i, j0-1] with its start lane.
            s, sstart = _lex_min(u_p, su_p, sat_add(a_p, b_left), bs_left)
        else:
            mn = jnp.minimum(prev_sh, prev)  # min(S[i-1,j-1], S[i-1,j])
            a, u = d, sat_add(d, mn)
            a_p, u_p = lax.associative_scan(_tropical_combine, (a, u))
            s = jnp.minimum(u_p, sat_add(a_p, b_left))  # fold in S[i, j0-1]
        hit = i == qlen - 1
        best = jnp.where(hit, jnp.minimum(best, jnp.min(s)), best)
        if track:
            if return_lastrow:
                lrow = jnp.where(hit, s, lrow)
                lstart = jnp.where(hit, sstart, lstart)
                return ((s, sstart, best, lrow, lstart, i + 1),
                        (pick(s), pick(sstart)))
            return (s, sstart, best, i + 1), (pick(s), pick(sstart))
        if return_lastrow:
            lrow = jnp.where(hit, s, lrow)
            return (s, best, lrow, i + 1), pick(s)
        return (s, best, i + 1), pick(s)

    if track:
        bstart = bstart.astype(jnp.int32)
        xs = (query[1:], bcol[1:], bcol[:-1], bstart[1:], bstart[:-1])
    else:
        xs = (query[1:], bcol[1:], bcol[:-1])
    if return_lastrow:
        lrow0 = jnp.where(qlen == 1, s0, jnp.full_like(s0, BIG))
        if track:
            (_, _, best, lrow, lstart, _), tail = lax.scan(
                row_step, (s0, st0, best, lrow0, st0, jnp.int32(1)), xs)
        else:
            (_, best, lrow, _), tail = lax.scan(
                row_step, (s0, best, lrow0, jnp.int32(1)), xs)
    else:
        if track:
            (_, _, best, _), tail = lax.scan(
                row_step, (s0, st0, best, jnp.int32(1)), xs)
        else:
            (_, best, _), tail = lax.scan(row_step, (s0, best, jnp.int32(1)),
                                          xs)
    if track:
        tail_v, tail_s = tail
        new_bcol = jnp.concatenate([pick(s0)[None], tail_v])
        new_bstart = jnp.concatenate([pick(st0)[None], tail_s])
        if return_lastrow:
            return new_bcol, new_bstart, best, lrow, lstart
        return new_bcol, new_bstart, best
    new_bcol = jnp.concatenate([pick(s0)[None], tail])
    if return_lastrow:
        return new_bcol, best, lrow
    return new_bcol, best


def sdtw_chunk_batch(queries, ref_chunk, qlens, carry, j0, m_total,
                     metric: str, excl_lo, excl_hi, clen=None):
    """Advance the batched carry by one chunk.

    ``carry`` is ``(bcol (nq, N), best (nq,))`` or, with the start lane,
    ``(bcol, bstart, best)`` — the lane is tracked iff it is present.
    ``clen`` (traced) is the chunk's true column count — see
    ``sdtw_rowscan_chunk``. A 2-D ``ref_chunk`` (nq, C) gives each query
    its own reference row over the same columns."""
    if len(carry) == 3:
        bcol, bstart, best = carry
        return _map_rows(
            lambda q, r, ql, bc, bs, be, lo, hi: sdtw_rowscan_chunk(
                q, r, bc, be, ql, j0, m_total, metric, lo, hi,
                bstart=bs, clen=clen),
            queries, ref_chunk, qlens, bcol, bstart, best, excl_lo, excl_hi)
    bcol, best = carry
    return _map_rows(
        lambda q, r, ql, bc, be, lo, hi: sdtw_rowscan_chunk(
            q, r, bc, be, ql, j0, m_total, metric, lo, hi, clen=clen),
        queries, ref_chunk, qlens, bcol, best, excl_lo, excl_hi)


def _map_rows(fn, queries, ref_chunk, *rest):
    """``fn(query, ref, *rest)`` mapped over the query rows; the reference
    is mapped too when it is 2-D (one row per query), else shared."""
    ref_axis = 0 if ref_chunk.ndim == 2 else None
    return jax.vmap(fn, in_axes=(0, ref_axis) + (0,) * len(rest))(
        queries, ref_chunk, *rest)


def sdtw_chunk_batch_topk(queries, ref_chunk, qlens, carry, j0, m_total,
                          metric: str, excl_lo, excl_hi, k: int,
                          excl_zone, excl_span: bool = False,
                          track_start: bool = False, clen=None,
                          return_lastrow: bool = False):
    """Advance the *top-K* carry by one chunk.

    The carry is ``(bcol, best, top_d, top_p, top_s)`` or — with
    ``track_start`` — ``(bcol, bstart, best, top_d, top_p, top_s)``.
    On top of the boundary-column hand-off, the carry holds a per-query
    match heap (top_d (nq, k), top_p (nq, k), top_s (nq, k)): the chunk's
    last DP row — the score of every alignment ending at each chunk
    column, with (when tracked) the start-pointer lane giving its span —
    is folded into the heap with exclusion-zone suppression
    (``repro.core.topk``; ``excl_zone`` is a per-query (nq,) radius, so a
    ragged bucket keeps each query's own zone; ``excl_span`` switches
    suppression to span overlap and requires ``track_start``). Without
    tracking, the heap's start lane stays -1 and the boundary carry keeps
    the untaxed value-only lane. End positions are global (``j0`` offsets
    the chunk), so the same code serves the in-process streamer and the
    sharded systolic pipeline. ``return_lastrow`` appends the (nq, C)
    candidate row (and, when tracked, its start lane) to the output —
    the streaming monitor's threshold-alert feed.
    """
    pos = j0 + jnp.arange(ref_chunk.shape[-1], dtype=jnp.int32)
    if track_start:
        bcol, bstart, best, top_d, top_p, top_s = carry

        def one(q, r, ql, bc, bs, be, lo, hi, hd, hp, hs, ez):
            nbc, nbs, nbe, lrow, lstart = sdtw_rowscan_chunk(
                q, r, bc, be, ql, j0, m_total, metric, lo, hi,
                return_lastrow=True, bstart=bs, clen=clen)
            nd, np_, ns = topk_merge(hd, hp, hs, lrow, pos, lstart, k, ez,
                                     excl_span)
            if return_lastrow:
                return nbc, nbs, nbe, nd, np_, ns, lrow, lstart
            return nbc, nbs, nbe, nd, np_, ns

        return _map_rows(one, queries, ref_chunk, qlens, bcol, bstart, best,
                         excl_lo, excl_hi, top_d, top_p, top_s, excl_zone)
    assert not excl_span, "span-overlap suppression needs the start lane"
    bcol, best, top_d, top_p, top_s = carry
    no_start = jnp.full_like(pos, -1)

    def one(q, r, ql, bc, be, lo, hi, hd, hp, hs, ez):
        nbc, nbe, lrow = sdtw_rowscan_chunk(
            q, r, bc, be, ql, j0, m_total, metric, lo, hi,
            return_lastrow=True, clen=clen)
        nd, np_, ns = topk_merge(hd, hp, hs, lrow, pos, no_start, k, ez)
        if return_lastrow:
            return nbc, nbe, nd, np_, ns, lrow
        return nbc, nbe, nd, np_, ns

    return _map_rows(one, queries, ref_chunk, qlens, bcol, best, excl_lo,
                     excl_hi, top_d, top_p, top_s, excl_zone)


def topk_fold_lastrow(heap, lastrow, lstarts, j0, k: int, excl_zone,
                      excl_span: bool = False):
    """Fold a batched (nq, C) candidate row into the top-K heap.

    ``lastrow`` is the DP's row ``qlen - 1`` over C reference columns
    (global columns ``[j0, j0 + C)``) — exactly what the rowscan chunk
    path harvests with ``return_lastrow=True`` and what the Pallas
    kernel's in-kernel last-row capture emits — and the merge performed
    here is the *same* ``topk_merge`` call the rowscan streaming path runs
    per chunk, so a Pallas-scored tile updates the heap bitwise-
    identically to the rowscan-scored one. ``lstarts`` is the candidate
    row's start-pointer lane (``None`` when the caller does not track
    spans — the heap's start lane then stays -1); ``excl_zone`` is the
    per-query (nq,) suppression radius.
    """
    hd, hp, hs = heap
    c = lastrow.shape[1]
    pos = j0 + jnp.arange(c, dtype=jnp.int32)
    if lstarts is None:
        lstarts = jnp.full_like(lastrow, -1, dtype=jnp.int32)

    def one(hd_, hp_, hs_, lr, ls, ez):
        return topk_merge(hd_, hp_, hs_, lr, pos, ls, k, ez, excl_span)

    return jax.vmap(one)(hd.astype(lastrow.dtype), hp, hs, lastrow, lstarts,
                         jnp.asarray(excl_zone, jnp.int32))


def default_excl_zone(qlens):
    """The documented default suppression radius: half the *true* query
    length, per query (not the padded bucket width — ragged dispatch must
    match the equivalent per-query call)."""
    return jnp.maximum(1, jnp.asarray(qlens, jnp.int32) // 2)


def sdtw_segment_topk(queries, segment, qlens, carry, j0, m_total,
                      metric: str, chunk: int, excl_lo, excl_hi, k: int,
                      excl_zone, excl_span: bool = False,
                      track_start: bool = False):
    """``sdtw_segment`` with the top-K heap riding the chunk carry."""
    n_tiles = segment.shape[0] // chunk
    tiles = segment.reshape(n_tiles, chunk)

    def step(c, xs):
        tile, t = xs
        return sdtw_chunk_batch_topk(queries, tile, qlens, c,
                                     j0 + t * chunk, m_total, metric,
                                     excl_lo, excl_hi, k, excl_zone,
                                     excl_span, track_start), None

    carry, _ = lax.scan(step, carry, (tiles, jnp.arange(n_tiles)))
    return carry


def sdtw_segment(queries, segment, qlens, carry, j0, m_total, metric: str,
                 chunk: int, excl_lo, excl_hi):
    """Stream a reference segment through the carry in ``chunk``-sized tiles.

    ``segment`` length must be a static multiple of ``chunk``; ``j0`` (the
    segment's global column offset) and ``m_total`` may be traced — this is
    what lets the sharded driver reuse the code with a per-device offset.
    Memory is O(nq·N + chunk) regardless of segment length (lax.scan).
    The start lane is tracked iff the carry includes it (3-tuple).
    """
    n_tiles = segment.shape[0] // chunk
    tiles = segment.reshape(n_tiles, chunk)

    def step(c, xs):
        tile, k = xs
        return sdtw_chunk_batch(queries, tile, qlens, c, j0 + k * chunk,
                                m_total, metric, excl_lo, excl_hi), None

    carry, _ = lax.scan(step, carry, (tiles, jnp.arange(n_tiles)))
    return carry


@functools.partial(jax.jit, static_argnames=("metric", "chunk", "top_k",
                                             "return_positions",
                                             "return_spans", "excl_mode"))
def sdtw_chunked(queries, reference, qlens=None, metric: str = "abs_diff",
                 chunk: int = 4096, excl_lo=None, excl_hi=None,
                 top_k: Optional[int] = None, excl_zone=None,
                 return_positions: bool = False,
                 return_spans: bool = False, excl_mode: str = "end"):
    """Batched sDTW over an arbitrarily long reference in bounded memory.

    The reference is padded to a multiple of ``chunk`` and scanned tile by
    tile under a single jitted shape; only the (nq, N) boundary column is
    carried between tiles. M = millions runs in O(nq·N + chunk) live memory.

    Top-K mode: with ``top_k=k`` the carry additionally holds a per-query
    (distances, ends, starts) heap (see ``repro.core.topk``); the call
    returns ``(dists (nq, k), positions (nq, k))``, best first, matches at
    least ``excl_zone + 1`` apart (``excl_zone``: scalar or (nq,); default
    half of each query's *true* length — or 0 with ``excl_mode='span'``,
    which keys suppression on span overlap instead of end distance). With
    only ``return_positions=True`` the top-1 pair is returned unstacked:
    ``(dists (nq,), positions (nq,))``. ``return_spans=True`` inserts the
    start-pointer lane into the result: ``(dists, starts, ends)`` (stacked
    (nq, k) with ``top_k``). The top-1 distance is bitwise-equal to the
    plain streaming result; its position is the leftmost end index
    attaining it, and its start the smallest row-0 column among the
    minimum-cost alignments ending there.
    """
    nq, n = queries.shape
    m = reference.shape[0]
    acc = accum_dtype(jnp.result_type(queries, reference))
    if qlens is None:
        qlens = jnp.full((nq,), n, jnp.int32)
    if excl_lo is None:
        excl_lo = jnp.full((nq,), -1, jnp.int32)
        excl_hi = jnp.full((nq,), -1, jnp.int32)
    n_tiles = -(-m // chunk)
    r_pad = jnp.pad(reference, (0, n_tiles * chunk - m))
    if top_k is None and not (return_positions or return_spans):
        carry = sdtw_carry_init(nq, n, acc)
        _, best = sdtw_segment(queries, r_pad, qlens, carry, 0, m, metric,
                               chunk, excl_lo, excl_hi)
        return best
    k = 1 if top_k is None else top_k
    if excl_zone is None:
        zone = (default_excl_zone(qlens) if excl_mode == "end"
                else jnp.zeros((nq,), jnp.int32))
    else:
        zone = jnp.broadcast_to(jnp.asarray(excl_zone, jnp.int32), (nq,))
    # The start lane is only paid for when starts are consumed — spans
    # requested, or span-overlap suppression (which selects on them).
    track = return_spans or excl_mode == "span"
    carry = (sdtw_carry_init(nq, n, acc, track_start=track)
             + topk_init(nq, k, acc))
    out = sdtw_segment_topk(
        queries, r_pad, qlens, carry, 0, m, metric, chunk, excl_lo,
        excl_hi, k, zone, excl_span=(excl_mode == "span"),
        track_start=track)
    top_d, top_p, top_s = out[-3:]
    if top_k is None:                       # top-1, unstacked
        if return_spans:
            return top_d[:, 0], top_s[:, 0], top_p[:, 0]
        return top_d[:, 0], top_p[:, 0]
    if return_spans:
        return top_d, top_s, top_p
    return top_d, top_p


# ---------------------------------------------------------------------------
# Batched front-ends.
# ---------------------------------------------------------------------------

_IMPLS = {"rowscan": sdtw_rowscan, "wavefront": sdtw_wavefront}


def sdtw_batch(queries, reference, qlens=None, metric: str = "abs_diff",
               impl: str = "rowscan", excl_lo=None, excl_hi=None,
               return_positions: bool = False, return_spans: bool = False):
    """Batched sDTW: (nq, N) queries against a shared (M,) reference.

    Queries are embarrassingly parallel (paper §II-D) — this is MATSA's
    reference-replication / query-pipelining axis, mapped to vmap. With
    ``return_positions=True`` returns ``(dists (nq,), end_positions (nq,))``;
    with ``return_spans=True`` returns ``(dists, starts, ends)``.
    """
    fn = _IMPLS[impl]
    nq, n = queries.shape
    if qlens is None:
        qlens = jnp.full((nq,), n, jnp.int32)
    if excl_lo is None:
        excl_lo = jnp.full((nq,), -1, jnp.int32)
        excl_hi = jnp.full((nq,), -1, jnp.int32)
    return jax.vmap(
        lambda qu, ql, lo, hi: fn(qu, reference, ql, metric, lo, hi,
                                  return_positions, return_spans)
    )(queries, qlens, excl_lo, excl_hi)


def self_join_windows(reference, window: int, stride: int = 1):
    """Extract sliding windows (the paper's self_join mode: slices of the
    reference compared against the reference itself).

    ``starts`` are window start positions in **sample** units — for
    ``stride > 1`` they are *not* consecutive window indices. Every
    consumer deriving exclusion zones from them (``self_join_exclusion``,
    ``matsa``, ``repro.search.profile``) must stay in sample space."""
    m = reference.shape[0]
    starts = jnp.arange(0, m - window + 1, stride)
    idx = starts[:, None] + jnp.arange(window)[None, :]
    return reference[idx], starts


def self_join_exclusion(starts, window: int, zone: int = None):
    """Trivial-match exclusion band per self-join window, in sample units.

    A window occupying samples ``[s, s + window)`` must not be matched
    against itself or a near-identical shifted copy; the matrix-profile
    convention bans reference columns within ``zone`` samples (default
    ``window // 2``) of the window's own extent.

    ``starts`` must be sample positions (what ``self_join_windows``
    returns), NOT window indices — the band is then stride-invariant:
    with ``stride > 1`` each window still bans exactly
    ``[s - zone, s + window + zone)`` *samples*, never a range scaled by
    the window-index spacing. Returns ``(excl_lo, excl_hi)`` int32
    arrays for ``engine.sdtw``'s half-open banned-column range.
    """
    starts = jnp.asarray(starts, jnp.int32)
    z = jnp.int32(window // 2 if zone is None else int(zone))
    lo = jnp.maximum(starts - z, 0)
    hi = starts + jnp.int32(window) + z
    return lo, hi
