"""Pallas TPU kernel for batched sDTW — MATSA's compute subarray, TPU-native.

Mapping of MATSA's mechanisms onto the TPU (DESIGN.md §2):

  * MATSA column-parallelism  → VPU lanes: each kernel invocation processes a
    (block_q × block_m) strip with the reference dimension vectorized across
    lanes and queries across sublanes/grid.
  * O(4M) linear data mapping → only two row vectors (prev/cur) + a boundary
    column live in VMEM; the N×M matrix is never materialised and HBM traffic
    is O(N + M) per query instead of O(N·M).
  * wavefront dependency-breaking → the per-row recurrence
        s[j] = d[j] + min(min(prev[j-1], prev[j]), s[j-1])
    is a first-order linear recurrence over the (min, +) semiring, solved
    by a parallel scan across the lane dimension (see *scan schemes*).
  * query pipelining → the Pallas grid double-buffers the next reference tile
    from HBM while the current one computes.

Grid: (num_query_blocks, num_ref_tiles); the tile dimension is innermost and
sequential. The DP boundary column lives in a persistent VMEM *scratch*
buffer (``scratch_shapes`` — allocated once for the whole grid, so it
carries across the sequential tile dimension exactly like MATSA's
inter-subarray pass gates, §III-B). The final tile copies the scratch into
the ``bound`` output so the cross-call chunk-carry protocol is unchanged.

Lane access (what Mosaic accepts): the query block and the boundary
scratch are padded to a multiple of 128 lanes by the wrapper, and the DP
rows are walked one aligned 128-lane window at a time
(``pl.ds(pl.multiple_of(w * 128, 128), 128)``): a window of queries and of
the boundary scratch is read once, each of its rows picks its query value
and boundary entry out of it with a one-hot lane mask and merges its new
boundary entry back with the same mask, and the window is written back
once. Mosaic lowers neither ``dynamic_slice`` nor a lane slice it cannot
prove 128-aligned, and per-row scratch traffic would cost more than the
in-register picks.

Scan schemes (both exactly associative over the tropical semiring, so
int32 results are bitwise-identical between them; float32 differs only in
summation order):

  * ``"shift"`` — Hillis-Steele doubling in log2(block_m) lane-shift
    steps: the scheme Mosaic compiles for the TPU.
  * ``"assoc"`` — ``lax.associative_scan`` (work-efficient odd-even
    recursion, O(block_m) combines): interpret mode only, where each
    shift step costs a full memory sweep and the work-efficient form is
    ~2× faster end to end. Mosaic cannot lower it (its recursion slices
    zero-width vectors); ``sdtw_pallas`` refuses it on the TPU.

Row tiling: ``row_tile`` consecutive DP rows of a window are unrolled per
loop iteration, amortizing the row loop's control overhead (and the
per-row scan set-up) over the tile. The per-row scans themselves stay sequential
(row r+1 consumes row r's output — the DP's true dependency).

In-kernel last-row capture (``want_lastrow``): the kernel additionally
emits row ``qlen - 1`` of the DP — the cost of a match *ending* at every
reference column, i.e. exactly the candidate row
``repro.core.sdtw.sdtw_chunk_batch_topk`` consumes — so top-K search
survivors and streaming monitor tiles can score on the kernel path
instead of falling back to the rowscan. The best/pos/start outputs are
harvested from this captured row once per tile (each query's ``qlen - 1``
row is unique), instead of the old per-row candidate bookkeeping.

Match spans (``track=True``): every DP lane becomes a lexicographic
``(value, start)`` pair — ``start`` is the row-0 reference column where the
cell's best path began, with value ties resolved toward the smaller start
(``repro.core.distances.lex_min``, the single shared rule). The start lane
rides the scan, the boundary column, and the cross-call chunk carry. The
plain variant keeps the untaxed value+position lanes.

Accumulates in float32 or saturating int32 (see core.distances).
Per-query exclusion zones are not supported here (the engine's dispatch
sends such calls to the rowscan path); the traced ``lead``/``rlen`` window masks a *leading* /
*trailing* band of columns, which is what the pruned search's halo groups
and right-padded streaming tails need.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from repro.core.distances import (INT_FAR, big, lex_min, sat_add,
                                  tropical_combine, tropical_combine_span)


#: Lane width of a TPU vector register: the query block and the boundary
#: scratch are read and written in aligned windows of this many lanes.
LANES = 128


def _pick(x, hit):
    """The lane of ``x`` selected by the one-hot ``hit``, as a (rows, 1)
    column. Exact: every other lane contributes zero."""
    return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _shift_lanes(x, k, fill):
    """``x`` moved ``k`` lanes right along axis 1; the ``k`` lanes it
    leaves at the left take ``fill`` (a scalar or a (rows, 1) column)."""
    sh = jnp.pad(x, ((0, 0), (k, 0)))[:, :x.shape[1]]
    return jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 1) < k, fill,
                     sh)


def _distance(q, r, metric):
    d = q - r
    if metric == "abs_diff":
        return jnp.abs(d)
    return d * d


def _tropical_row_scan(a, u, su, big_val):
    """Inclusive Hillis-Steele scan of f_j(x) = min(u_j, a_j + x) along
    lanes. With ``su`` (a start lane) the u-component carries it
    lexicographically; with ``su=None`` it is the plain value scan.

    Returns (a_pref, u_pref, su_pref|None) with u_pref[j] = s_j assuming
    x_init folded in by the caller via (lex)min(u_pref, a_pref + x_init).
    Identity = (a=0, u=BIG, su=INT_FAR).
    """
    bm = a.shape[-1]
    shift = 1
    while shift < bm:
        a_sh = _shift_lanes(a, shift, 0)
        u_sh = _shift_lanes(u, shift, big_val)
        if su is None:
            u = jnp.minimum(u, sat_add(a, u_sh))
        else:
            su_sh = _shift_lanes(su, shift, INT_FAR)
            u, su = lex_min(u, su, sat_add(a, u_sh), su_sh)
        a = sat_add(a, a_sh)
        shift *= 2
    return a, u, su


def _tropical_row_scan_assoc(a, u, su, big_val):
    """Work-efficient variant of ``_tropical_row_scan`` via
    ``lax.associative_scan`` over the shared semiring combine. Same
    contract, same int32 bits (tropical min/+ is exactly associative);
    ~2× fewer memory sweeps than the shift scheme off-TPU."""
    if su is None:
        a_p, u_p = lax.associative_scan(tropical_combine, (a, u), axis=1)
        return a_p, u_p, None
    a_p, u_p, su_p = lax.associative_scan(tropical_combine_span, (a, u, su),
                                          axis=1)
    return a_p, u_p, su_p


_SCAN_SCHEMES = {"shift": _tropical_row_scan,
                 "assoc": _tropical_row_scan_assoc}


def _sdtw_kernel(metric, n, block_m, track, want_lastrow, scheme, row_tile,
                 *refs):
    """One (query_block, ref_tile) cell of the grid.

    Refs, in order (``track=False`` omits every *start* ref — the lanes
    marked ⊕ exist only in the span variant; the ``lastrow`` outputs only
    with ``want_lastrow``):

    q_ref:       (block_q, Np)  queries (VMEM), N padded to Np, a multiple
                                of ``LANES`` — read one window at a time
    r_ref:       (1, block_m)   reference tile (VMEM), broadcast over the
                                query rows; (block_q, block_m) when each
                                query row has its own reference row
    qlen_ref:    (block_q, 1)   true query lengths
    rlen_ref:    (1, 1)         true reference length (columns >= rlen are
                                masked; the carry exits at column rlen-1)
    lead_ref:    (1, 1)         leading banned columns (columns < lead are
                                masked — the pruned search's left halo pad;
                                0 for ordinary calls)
    off_ref:     (1, 1)         global column offset of this reference slice
                                (chunk-carry streaming) — reported match
                                positions are ``off + local column``
    bcol_in_ref: (block_q, Np)  carry in: boundary column entering this call
                                (BIG for a fresh start)
    bstart_in_ref:(block_q, Np)⊕ carry in: start lane of that boundary
                                column (INT_FAR for a fresh start)
    best_in_ref: (block_q, 1)   carry in: running per-query best
    pos_in_ref:  (block_q, 1)   carry in: end position of that best (-1 for
                                a fresh start)
    start_in_ref:(block_q, 1) ⊕ carry in: start position of that best (-1)
    out_ref:     (block_q, 1)   running per-query best (min over last valid
                                row)
    bound_ref:   (block_q, Np)  output: boundary column for the next slice
                                (written from the VMEM scratch on the final
                                tile — the chunk-carry protocol)
    bound_start_ref:(bq, Np)  ⊕ output: start lane of the boundary column
    pos_ref:     (block_q, 1)   output: global end position of the best
                                match (leftmost column attaining it);
                                updated only on strict improvement so
                                earlier slices/tiles win ties, matching the
                                rowscan's leftmost ``argmin``
    start_ref:   (block_q, 1) ⊕ output: global start position of that match
    lastrow_ref: (block_q, block_m) output per tile: row ``qlen - 1`` of
                                the DP (BIG at masked columns) — the
                                candidate row for top-K folding
    lastrow_start_ref: ⊕        its start-pointer lane
    bscratch:    (block_q, Np)  VMEM scratch: the live boundary column,
                                persistent across the sequential tile grid
    bsscratch:   (block_q, Np)⊕ VMEM scratch: its start lane
    """
    it = iter(refs)
    q_ref = next(it)
    r_ref = next(it)
    qlen_ref = next(it)
    rlen_ref = next(it)
    lead_ref = next(it)
    off_ref = next(it)
    bcol_in_ref = next(it)
    bstart_in_ref = next(it) if track else None
    best_in_ref = next(it)
    pos_in_ref = next(it)
    start_in_ref = next(it) if track else None
    out_ref = next(it)
    bound_ref = next(it)
    bound_start_ref = next(it) if track else None
    pos_ref = next(it)
    start_ref = next(it) if track else None
    lastrow_ref = next(it) if want_lastrow else None
    lastrow_start_ref = next(it) if (want_lastrow and track) else None
    bscratch = next(it)
    bsscratch = next(it) if track else None

    t = pl.program_id(1)
    nt = pl.num_programs(1)
    acc = out_ref.dtype
    BIG = big(acc)
    bq = q_ref.shape[0]
    INT_FAR_ = jnp.int32(INT_FAR)
    scan = _SCAN_SCHEMES[scheme]

    # Loop invariants, read/computed once per tile.
    r = r_ref[...].astype(acc)                       # (1 | bq, bm)
    qlen = qlen_ref[...].astype(jnp.int32)           # (bq, 1)
    rlen = rlen_ref[0, 0]
    lead = lead_ref[0, 0]
    off = off_ref[0, 0]
    j_local = t * block_m + lax.broadcasted_iota(jnp.int32, (1, block_m), 1)
    col_ok = (j_local >= lead) & (j_local < rlen)    # (1, bm)
    gcol = jnp.broadcast_to(off + j_local, (bq, block_m))
    lane = lax.broadcasted_iota(jnp.int32, (bq, block_m), 1)
    # This tile's last *valid* column is the next boundary (the returned
    # carry must be S[:, rlen-1], not a BIG padding lane, for cross-call
    # chaining to be exact); a tile past rlen keeps the old boundary.
    at_exit = lane == jnp.clip(rlen - 1 - t * block_m, 0, block_m - 1)
    in_tile = t * block_m < rlen

    @pl.when(t == 0)
    def _init():
        out_ref[...] = best_in_ref[...]
        pos_ref[...] = pos_in_ref[...]
        bscratch[...] = bcol_in_ref[...]
        if track:
            start_ref[...] = start_in_ref[...]
            bsscratch[...] = bstart_in_ref[...]

    def one_row(i, hit, qwin, bwin, bswin, prev, pstart, b_im1, bs_im1,
                lrow, lstart):
        """DP row ``i``; ``hit`` is its one-hot lane in the current window.
        ``bwin``/``bswin`` hold the window's boundary entries: lane ``i``
        and above still from the previous tile, the lanes below already
        overwritten with this tile's exit. ``b_im1``/``bs_im1`` are row
        i-1's previous-tile entries. Returns the updated state, with row
        i's previous-tile entries as the next row's ``b_im1``/``bs_im1``
        (``None`` for every start lane in the plain variant)."""
        qi = _pick(qwin, hit)                                # (bq, 1)
        d = _distance(qi, r, metric)                         # (bq, bm)
        d = jnp.where(col_ok, d, BIG)
        b_row = _pick(bwin, hit)
        bs_row = _pick(bswin, hit) if track else None

        # prev shifted right by one lane; lane 0 takes the diagonal boundary.
        prev_sh = _shift_lanes(prev, 1, b_im1)
        if track:
            pstart_sh = _shift_lanes(pstart, 1, bs_im1)
            mn, mns = lex_min(prev_sh, pstart_sh, prev, pstart)
        else:
            mn, mns = jnp.minimum(prev_sh, prev), None

        u = sat_add(d, mn)
        a_p, u_p, su_p = scan(d, u, mns, BIG)
        sstart = None
        if track:
            s_rec, ss_rec = lex_min(u_p, su_p, sat_add(a_p, b_row), bs_row)
            sstart = jnp.where(i == 0, gcol, ss_rec)
        else:
            s_rec = jnp.minimum(u_p, sat_add(a_p, b_row))
        s = jnp.where(i == 0, d, s_rec)                      # free-start row
        s = jnp.where(col_ok, s, BIG)
        if track:
            sstart = jnp.where(col_ok, sstart, INT_FAR_)

        # Capture row qlen-1 (each query hits it exactly once per tile).
        at_last = i == qlen - 1
        lrow = jnp.where(at_last, s, lrow)
        if track:
            lstart = jnp.where(at_last, sstart, lstart)

        bwin = jnp.where(hit, jnp.where(in_tile, _pick(s, at_exit), b_row),
                         bwin)
        if track:
            bswin = jnp.where(
                hit, jnp.where(in_tile, _pick(sstart, at_exit), bs_row),
                bswin)
        return bwin, bswin, s, sstart, b_row, bs_row, lrow, lstart

    def window(w0, rows, state):
        """DP rows ``w0 .. w0 + rows - 1``: the aligned ``LANES``-wide
        window of the queries and of the boundary scratch holding them is
        read once and written back once. ``row_tile`` rows are unrolled
        per loop iteration (a static remainder closes the window)."""
        wl = pl.ds(w0, LANES)
        qwin = q_ref[:, wl].astype(acc)
        wlane = lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)

        def rows_from(r0, width, st):
            for rr in range(width):
                st = one_row(w0 + r0 + rr, wlane == r0 + rr, qwin, *st)
            return st

        n_main, n_tail = divmod(rows, row_tile)
        st = (bscratch[:, wl], bsscratch[:, wl] if track else None) + state
        st = lax.fori_loop(
            0, n_main, lambda ib, st: rows_from(ib * row_tile, row_tile, st),
            st)
        if n_tail:
            st = rows_from(n_main * row_tile, n_tail, st)
        bscratch[:, wl] = st[0]
        if track:
            bsscratch[:, wl] = st[1]
        return st[2:]

    # Row state: (prev, pstart, b_im1, bs_im1, lrow, lstart); the start
    # lanes are None in the plain variant, so its loop carry stays lean.
    state = (jnp.full((bq, block_m), BIG, acc),
             jnp.full((bq, block_m), INT_FAR_, jnp.int32) if track else None,
             jnp.full((bq, 1), BIG, acc),
             jnp.full((bq, 1), INT_FAR_, jnp.int32) if track else None,
             jnp.full((bq, block_m), BIG, acc),
             jnp.full((bq, block_m), INT_FAR_, jnp.int32) if track else None)
    n_full, n_last = divmod(n, LANES)
    if n_full:
        state = lax.fori_loop(
            0, n_full,
            lambda w, st: window(pl.multiple_of(w * LANES, LANES), LANES, st),
            state)
    if n_last:
        state = window(n_full * LANES, n_last, state)
    lrow, lstart = state[4], state[5]

    # Harvest best / end position / start from the captured last row, once
    # per tile (the old kernel paid this bookkeeping on every DP row).
    best0 = out_ref[...]
    pos0 = pos_ref[...]
    row_min = jnp.min(lrow, axis=1, keepdims=True)
    is_min = lrow == row_min
    cand = jnp.min(jnp.where(is_min, gcol, INT_FAR_), axis=1, keepdims=True)
    improve = row_min < best0      # strict: earlier tiles/slices keep ties
    out_ref[...] = jnp.minimum(best0, row_min)
    pos_ref[...] = jnp.where(improve, cand.astype(jnp.int32), pos0)
    if track:
        start0 = start_ref[...]
        at_cand = is_min & (gcol == cand)
        cand_start = jnp.min(jnp.where(at_cand, lstart, INT_FAR_), axis=1,
                             keepdims=True)
        start_ref[...] = jnp.where(improve, cand_start.astype(jnp.int32),
                                   start0)
    if want_lastrow:
        lastrow_ref[...] = lrow
        if track:
            lastrow_start_ref[...] = lstart

    @pl.when(t == nt - 1)
    def _emit_bound():
        bound_ref[...] = bscratch[...]
        if track:
            bound_start_ref[...] = bsscratch[...]
