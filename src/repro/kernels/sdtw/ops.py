"""Jitted wrapper around the sDTW Pallas kernel.

Handles padding/alignment, BlockSpec plumbing, dtype promotion, the
execution mode (``interpret=None`` asks ``interpret_mode``: Mosaic-compiled
on the TPU, Pallas interpret mode on the CPU where the tests run, refused
on any other backend), and the chunk-carry protocol: a call may start from a
(boundary-column, best) carry produced by a previous call over an earlier
reference slice and return the carry for the next slice, so an arbitrarily
long reference can be streamed through fixed-shape kernel launches — the
same O(N) boundary-column hand-off MATSA performs between subarrays
(§III-B), lifted to the call boundary. In span mode the carry includes the
DP start-pointer lane, so streamed slices report exact global match
spans; the plain variant keeps the untaxed value+position lanes.

Auto-tuning (``block_q``/``block_m``/``scan_scheme``/``row_tile`` default
to ``None``): with ``tune='off'`` (the kernel-level default) the legacy
hand-tuned constants apply — on TPU the sublane-aligned (8, 512) block
with the Hillis-Steele ``"shift"`` scan and ``row_tile=8``; in interpret
mode (CPU) the block is fitted to the actual batch (no sublane
constraint to respect) with a tile large enough to cover the reference up
to a working-set budget, the work-efficient ``"assoc"`` scan, and no row
unrolling (XLA-CPU gains nothing from it). With ``tune='model'`` (what
``engine.sdtw`` passes by default) the unset knobs come from the
``repro.tune`` oracle instead: a tuning-table hit for this (backend,
metric, dtype, shape bucket), else the analytical cost model's
ranked pick (``tune='measure'`` is downgraded to the table here — this
resolves at trace time, where measuring would time tracing; the engine
runs measured refinement *before* dispatch). Explicit knobs always win.
Every configuration produces bitwise-identical int32 results — schemes
and block shapes differ only in float32 summation order, so tuning can
change speed but never answers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distances import INT_FAR, accum_dtype, big
from .sdtw import LANES, _sdtw_kernel

DEFAULT_BLOCK_Q = 8     # sublane-aligned query block (TPU)
DEFAULT_BLOCK_M = 512   # lane-aligned reference tile (multiple of 128, TPU)
DEFAULT_ROW_TILE = 8    # DP rows unrolled per loop iteration (TPU)

#: Interpret-mode working-set budget: block_q * block_m is kept at or
#: under this many accumulator elements (~8 MB int32 per live row array).
INTERPRET_ELEM_BUDGET = 1 << 21
INTERPRET_MAX_BLOCK_Q = 32


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pow2_at_least(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def interpret_mode(backend: str | None = None) -> bool:
    """Whether the kernel runs in Pallas interpret mode on ``backend``
    (default: JAX's default backend). The one place this is decided:
    the CPU interprets the kernel, the TPU compiles it with Mosaic, and
    any other backend has no lowering and is refused — never silently
    interpreted on an accelerator."""
    backend = jax.default_backend() if backend is None else backend
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the sDTW Pallas kernel has no lowering for backend {backend!r}: "
        "it compiles for 'tpu' and is interpreted on 'cpu' only")


def resolve_blocks(b: int, m: int, block_q, block_m, scan_scheme, row_tile,
                   interpret: bool, *, n=None, metric: str = "abs_diff",
                   dtype: str = "int32", tune: str = "off",
                   span: bool = False, lastrow: bool = False,
                   rowref: bool = False):
    """Fill in the auto (None) kernel tuning knobs for this call shape.

    Returns ``(block_q, block_m, scan_scheme, row_tile)``. With
    ``tune != 'off'`` (and ``n`` known) the unset knobs come from the
    ``repro.tune`` oracle — table hit, else cost-model pick, priced for
    the kernel variant (``span``, ``lastrow``, and ``rowref``: one
    reference row per query row); explicit (non-None) knobs always win.
    Otherwise the legacy heuristics apply:
    interpret mode has no sublane/lane alignment to respect, so the query
    block fits the batch exactly (padding queries to a multiple of 8
    would be pure wasted compute) and the reference tile grows to cover
    the reference up to ``INTERPRET_ELEM_BUDGET`` (fewer boundary-column
    crossings, wider work-efficient scans).
    """
    if (tune != "off" and n is not None
            and (block_q is None or block_m is None
                 or scan_scheme is None or row_tile is None)):
        from repro.tune import tuned_blocks
        tq, tm, ts, tr = tuned_blocks(
            b, m, n=int(n), backend="tpu" if not interpret else "interpret",
            metric=metric, dtype=dtype, mode=tune, span=span,
            lastrow=lastrow, rowref=rowref)
        block_q = tq if block_q is None else block_q
        block_m = tm if block_m is None else block_m
        scan_scheme = ts if scan_scheme is None else scan_scheme
        row_tile = tr if row_tile is None else row_tile
    if block_q is None:
        block_q = (DEFAULT_BLOCK_Q if not interpret
                   else max(1, min(INTERPRET_MAX_BLOCK_Q, b)))
    if block_m is None:
        if not interpret:
            block_m = DEFAULT_BLOCK_M
        else:
            # Largest power of two keeping block_q * block_m at or under
            # the budget (rounding the quotient *up* would overshoot by
            # up to 1.5x for non-power-of-two batches). The floor is 16
            # (the block_m minimum), not 512: flooring the *quotient* at
            # 512 let an explicit block_q > 4096 push block_q * block_m
            # past INTERPRET_ELEM_BUDGET.
            budget = max(16, INTERPRET_ELEM_BUDGET // block_q)
            budget_pow2 = 1 << (budget.bit_length() - 1)
            block_m = min(max(16, _pow2_at_least(m)), budget_pow2)
    if scan_scheme is None:
        scan_scheme = "shift" if not interpret else "assoc"
    if row_tile is None:
        row_tile = DEFAULT_ROW_TILE if not interpret else 1
    return block_q, block_m, scan_scheme, row_tile


def pallas_carry_init(b: int, n: int, dtype, track_start: bool = False):
    """Fresh kernel chunk carry for a (b, N) query batch.

    ``(bcol (b, N), best (b,), pos (b,))`` — or the 5-tuple
    ``(bcol, bstart, best, pos, start)`` with ``track_start`` — exactly
    the structure ``sdtw_pallas(return_carry=True)`` emits, so a host loop
    can seed its first call with a real pytree (one compiled executable
    for every slice, first included) instead of ``carry=None``.
    """
    acc = accum_dtype(dtype)
    BIG = big(acc)
    bcol = jnp.full((b, n), BIG, acc)
    best = jnp.full((b,), BIG, acc)
    pos = jnp.full((b,), -1, jnp.int32)
    if not track_start:
        return bcol, best, pos
    bstart = jnp.full((b, n), INT_FAR, jnp.int32)
    start = jnp.full((b,), -1, jnp.int32)
    return bcol, bstart, best, pos, start


@functools.partial(
    jax.jit,
    static_argnames=("metric", "block_q", "block_m", "interpret",
                     "return_carry", "return_positions", "return_spans",
                     "track_start", "scan_scheme", "row_tile",
                     "return_lastrow", "tune"))
def sdtw_pallas(queries, reference, qlens=None, metric: str = "abs_diff",
                block_q: int | None = None,
                block_m: int | None = None,
                interpret: bool | None = None,
                carry=None,
                return_carry: bool = False,
                ref_offset=0,
                return_positions: bool = False,
                return_spans: bool = False,
                track_start: bool = False,
                ref_len=None,
                ref_lead=0,
                scan_scheme: str | None = None,
                row_tile: int | None = None,
                return_lastrow: bool = False,
                tune: str = "off"):
    """Batched sDTW on TPU via Pallas. queries (B, N), reference (M,) → (B,).

    A 2-D reference ``(B, M)`` gives every query row its own reference
    row (the same columns, so ``ref_offset``/``ref_len``/``ref_lead`` are
    shared): rows of independent streams then share one launch block.
    The kernel reads a ``(block_q, block_m)`` reference tile instead of
    the broadcast ``(1, block_m)`` one; a 1-D reference compiles the
    shared-reference kernel unchanged (the choice is made at trace time
    from the reference's rank).

    VMEM working set per grid cell ≈
    ``block_q · (3·block_m + 3·N)`` accumulator words plain,
    ``block_q · (6·block_m + 5·N)`` in span mode (the start lanes are
    int32; N is padded to a multiple of 128 lanes): the boundary column
    and (span mode) its start lane live in persistent VMEM *scratch*,
    each DP row reading and writing the aligned 128-lane window that
    holds its entry, and the row loop keeps ~3 (plain) / ~6 (span)
    block-wide row vectors live (prev / captured-last-row / scan
    temporaries, plus the start lanes). ``return_lastrow`` adds a
    double-buffered ``block_q · block_m`` output block (+ its int32 start
    lane in span mode), and a per-row reference a double-buffered
    ``block_q · block_m`` input tile. Block shapes must be chosen so this
    fits (~16 MB VMEM on v5e); the TPU defaults handle N ≤ 48K (plain) /
    N ≤ 24K (spans) comfortably.
    ``repro.tune.KernelCostModel.vmem_words`` prices candidates with this
    same formula, so any config the autotuner proposes fits by construction.

    Chunk-carry protocol: ``carry`` is an optional
    ``(bcol (B, N), best (B,), pos (B,))`` triple — the DP boundary column
    S[:, -1] of the reference slice processed so far, the running
    per-query best, and the global end position of that best (a legacy
    ``(bcol, best)`` pair is accepted and seeds positions at -1;
    ``pallas_carry_init`` builds a fresh one explicitly). In span mode
    (``return_spans=True``, or ``track_start=True`` to track without
    changing the primary result, e.g. mid-stream) the carry is the
    5-tuple ``(bcol, bstart, best, pos, start)`` with the boundary
    column's start-pointer lane and the global start of the running best;
    passing a 5-tuple carry selects span mode by itself. Passing the
    carry returned by a previous call (``return_carry=True``) continues
    the recurrence as if the two reference slices had been one array.
    ``ref_offset`` is the global column index of ``reference[0]`` (traced;
    no recompile per slice) so reported positions are global. ``ref_len``
    (traced, default the full array) marks only the first ``ref_len``
    columns of ``reference`` as real: the kernel masks columns ≥ rlen and
    exits its carry at column ``rlen - 1``, so a streaming caller can
    right-pad variable-size slices to one static shape and still chain the
    carry exactly — no recompile per fed chunk length. ``ref_lead``
    (traced, default 0) additionally masks the first ``ref_lead`` columns
    — the left padding of a pruned-search halo group; it assumes a fresh
    carry (the pad columns behave like the implicit BIG columns before the
    reference starts).

    With ``return_positions=True`` the primary result is a
    ``(dists (B,), end_positions (B,))`` pair; with ``return_spans=True``
    it is a ``(dists, starts, ends)`` triple.

    ``return_lastrow=True`` appends the in-kernel last-row capture to the
    return: the (B, M) candidate row — the DP's row ``qlen - 1``, i.e. the
    cost of a match *ending* at each reference column (BIG at masked
    columns), plus its (B, M) start lane in span mode. This is the same
    row ``repro.core.sdtw.sdtw_chunk_batch_topk`` harvests, so top-K
    consumers fold it with the identical ``topk_fold_lastrow`` merge.
    Return order: ``res[, new_carry][, lastrow[, lastrow_starts]]``.
    """
    if interpret is None:
        interpret = interpret_mode()
    b, n = queries.shape
    m = reference.shape[-1]
    rowref = reference.ndim == 2
    if rowref and reference.shape[0] != b:
        raise ValueError(f"a per-row reference needs one row per query: "
                         f"got {reference.shape[0]} rows for {b} queries")
    acc = accum_dtype(jnp.result_type(queries, reference))
    BIG = big(acc)
    block_q, block_m, scan_scheme, row_tile = resolve_blocks(
        b, m, block_q, block_m, scan_scheme, row_tile, interpret,
        n=n, metric=metric,
        dtype=str(jnp.result_type(queries, reference)), tune=tune,
        span=return_spans or track_start, lastrow=return_lastrow,
        rowref=rowref)
    if scan_scheme == "assoc" and not interpret:
        raise ValueError(
            "scan_scheme='assoc' does not compile for the TPU (Mosaic "
            "cannot lower lax.associative_scan inside the kernel); use "
            "'shift' there — 'assoc' is an interpret-mode scheme")

    carry = tuple(carry) if carry is not None else ()
    track = return_spans or track_start or len(carry) == 5
    bstart = pos = start = None
    if len(carry) == 5:
        bcol, bstart, best, pos, start = carry
    elif len(carry) == 3:               # (bcol, best, pos) triple
        bcol, best, pos = carry
    elif len(carry) == 2:               # legacy (bcol, best) pair
        bcol, best = carry
    elif len(carry) == 0:
        bcol = jnp.full((b, n), BIG, acc)
        best = jnp.full((b,), BIG, acc)
    else:
        raise ValueError(f"carry must have 2, 3 or 5 elements, got "
                         f"{len(carry)}")
    if pos is None:
        pos = jnp.full((b,), -1, jnp.int32)
    bcol = bcol.astype(acc)
    best = best.astype(acc)
    pos = pos.astype(jnp.int32)
    if track:
        if bstart is None:
            bstart = jnp.full((b, n), INT_FAR, jnp.int32)
        if start is None:
            start = jnp.full((b,), -1, jnp.int32)
        bstart = bstart.astype(jnp.int32)
        start = start.astype(jnp.int32)
    if qlens is None:
        qlens = jnp.full((b,), n, jnp.int32)
    bp = _ceil_to(b, block_q)
    mp = _ceil_to(max(m, block_m), block_m)
    # Queries and boundary column are lane-padded so the kernel can read
    # them as aligned LANES-wide windows (the padding is never a DP row).
    npad = _ceil_to(n, LANES)

    q_pad = jnp.zeros((bp, npad), queries.dtype).at[:b, :n].set(queries)
    if rowref:
        r_pad = jnp.zeros((bp, mp), reference.dtype).at[:b, :m].set(
            reference)
    else:
        r_pad = jnp.zeros((1, mp), reference.dtype).at[0, :m].set(reference)
    qlen_pad = jnp.ones((bp, 1), jnp.int32).at[:b, 0].set(qlens)
    rlen = jnp.full((1, 1), m if ref_len is None else ref_len, jnp.int32)
    lead = jnp.full((1, 1), ref_lead, jnp.int32)
    off = jnp.full((1, 1), ref_offset, jnp.int32)
    bcol_pad = jnp.full((bp, npad), BIG, acc).at[:b, :n].set(bcol)
    best_pad = jnp.full((bp, 1), BIG, acc).at[:b, 0].set(best)
    pos_pad = jnp.full((bp, 1), -1, jnp.int32).at[:b, 0].set(pos)

    grid = (bp // block_q, mp // block_m)
    kernel = functools.partial(_sdtw_kernel, metric, n, block_m, track,
                               return_lastrow, scan_scheme, row_tile)

    col_spec = pl.BlockSpec((block_q, npad), lambda qb, t: (qb, 0))
    scalar_spec = pl.BlockSpec((block_q, 1), lambda qb, t: (qb, 0))
    one_spec = pl.BlockSpec((1, 1), lambda qb, t: (0, 0))
    row_spec = pl.BlockSpec((block_q, block_m), lambda qb, t: (qb, t))
    tile_spec = (row_spec if rowref
                 else pl.BlockSpec((1, block_m), lambda qb, t: (0, t)))

    inputs = [q_pad, r_pad, qlen_pad, rlen, lead, off, bcol_pad]
    in_specs = [col_spec, tile_spec, scalar_spec, one_spec, one_spec,
                one_spec, col_spec]
    if track:
        bstart_pad = jnp.full((bp, npad), INT_FAR,
                              jnp.int32).at[:b, :n].set(bstart)
        inputs += [bstart_pad]
        in_specs += [col_spec]
    inputs += [best_pad, pos_pad]
    in_specs += [scalar_spec, scalar_spec]
    if track:
        start_pad = jnp.full((bp, 1), -1, jnp.int32).at[:b, 0].set(start)
        inputs += [start_pad]
        in_specs += [scalar_spec]

    out_specs = [scalar_spec, col_spec]
    out_shape = [jax.ShapeDtypeStruct((bp, 1), acc),
                 jax.ShapeDtypeStruct((bp, npad), acc)]
    if track:
        out_specs += [col_spec]
        out_shape += [jax.ShapeDtypeStruct((bp, npad), jnp.int32)]
    out_specs += [scalar_spec]
    out_shape += [jax.ShapeDtypeStruct((bp, 1), jnp.int32)]
    if track:
        out_specs += [scalar_spec]
        out_shape += [jax.ShapeDtypeStruct((bp, 1), jnp.int32)]
    if return_lastrow:
        out_specs += [row_spec]
        out_shape += [jax.ShapeDtypeStruct((bp, mp), acc)]
        if track:
            out_specs += [row_spec]
            out_shape += [jax.ShapeDtypeStruct((bp, mp), jnp.int32)]

    scratch_shapes = [pltpu.VMEM((block_q, npad), acc)]
    if track:
        scratch_shapes += [pltpu.VMEM((block_q, npad), jnp.int32)]

    # A fixed name, so that a profiler trace shows the kernel as
    # ``sdtw_pallas`` whichever jitted function calls it.
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        interpret=interpret, name="sdtw_pallas",
    )(*inputs)
    outs = list(outs)
    out = outs.pop(0)
    bound = outs.pop(0)
    bound_start = outs.pop(0) if track else None
    pos_out = outs.pop(0)
    start_out = outs.pop(0) if track else None
    lastrow = outs.pop(0) if return_lastrow else None
    lastrow_start = outs.pop(0) if (return_lastrow and track) else None

    dist = out[:b, 0]
    end_pos = pos_out[:b, 0]
    if return_spans:
        res = (dist, start_out[:b, 0], end_pos)
    elif return_positions:
        res = (dist, end_pos)
    else:
        res = dist
    extras = []
    if return_carry:
        if track:
            extras.append((bound[:b, :n], bound_start[:b, :n], dist,
                           end_pos, start_out[:b, 0]))
        else:
            extras.append((bound[:b, :n], dist, end_pos))
    if return_lastrow:
        extras.append(lastrow[:b, :m])
        if track:
            extras.append(lastrow_start[:b, :m])
    if extras:
        return (res, *extras)
    return res
