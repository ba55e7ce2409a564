"""Multi-device sDTW: one systolic pipeline builder on a (dp, mp) mesh.

Each device along the systolic (``mp``) axis owns one contiguous reference
segment (padded to a multiple of the streaming chunk). The sDTW recurrence
is sequential along the reference, so a single query batch must visit the
``mp`` devices in order — but batches are independent, which makes the
schedule a classic systolic pipeline: the query set is split into
microbatches, device d processes microbatch t − d at tick t, and the
(boundary-column, best) chunk carry of ``repro.core.sdtw`` is handed to the
right-hand neighbour with one ``lax.ppermute`` per tick. The inter-device
protocol is *identical* to the intra-device chunk carry — a device is just
a very large chunk — mirroring MATSA's inter-subarray pass gates scaled up
to inter-accelerator links.

The optional data-parallel (``dp``) axis crosses that pipeline with query
replication: microbatch slots are sharded over ``dp`` rows (the reference
is replicated within a row), each row runs its own systolic schedule over
its slice of the queries, and the out-spec concatenation over ``dp`` is the
final harvest — queries never communicate across rows because each query's
DP is independent.

Steady-state all ``mp`` devices are busy; pipeline fill/drain costs S − 1
of n_micro + S − 1 ticks. Devices compute garbage during fill (clipped
microbatch indices, zero-filled ppermute carries); only the last device's
in-window ticks are harvested, so the garbage never reaches the output.

Every sharded entry point — ``sdtw_sharded`` (batch), ``sdtw_sharded_feed``
(streaming), top-K, spans — instantiates the ONE builder below
(``build_pipeline``) with an entry policy (``fresh`` carries per microbatch
vs ``carry`` handed in by the caller) and a harvest policy (final
``result`` vs the full ``carry`` tuple). Compiled pipelines live in a
bounded cache keyed on the mesh *fingerprint* (axis names + device ids),
not live Mesh objects — see ``clear_pipeline_cache``/``_cache_size``.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.distances import accum_dtype
from repro.core.sdtw import (default_excl_zone, sdtw_carry_init,
                             sdtw_segment, sdtw_segment_topk)
from repro.core.topk import topk_init
from repro.distributed.collectives import neighbor_perm, psum_harvest
from repro.distributed.sharding import pipeline_axes


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def default_mesh(axis: str = "ref") -> Mesh:
    """1-D mesh over every local device, reference axis sharded."""
    return Mesh(np.asarray(jax.devices()), (axis,))


# ---------------------------------------------------------------------------
# Schedule: microbatch layout + padding/reshape/unpad glue
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelineSchedule:
    """Microbatch layout for one pipeline launch.

    ``slots = n_dp * n_micro`` microbatch slots of ``mb`` queries each;
    slot s holds queries [s*mb, (s+1)*mb), dp row r owns slots
    [r*n_micro, (r+1)*n_micro). ``pack``/``unpack`` are inverses around
    the sharded call, so results come back in query order regardless of
    the (dp, mp, n_micro) factorization — which is what makes the sharded
    path bitwise schedule-invariant for int32.
    """
    dp_axis: Optional[str]
    mp_axis: str
    n_dp: int
    n_mp: int
    n_micro: int
    mb: int
    nq: int

    @property
    def slots(self) -> int:
        return self.n_dp * self.n_micro

    def pack(self, arr, fill=0):
        """Pad a (nq, ...) array to slots*mb rows, reshape (slots, mb, ...)."""
        arr = jnp.asarray(arr)
        pad = self.slots * self.mb - arr.shape[0]
        widths = ((0, pad),) + ((0, 0),) * (arr.ndim - 1)
        padded = jnp.pad(arr, widths, constant_values=fill)
        return padded.reshape((self.slots, self.mb) + arr.shape[1:])

    def unpack(self, out):
        """Inverse of ``pack`` over a pytree of (slots, mb, ...) leaves."""
        flat = self.slots * self.mb
        return jax.tree.map(
            lambda o: o.reshape((flat,) + o.shape[2:])[:self.nq], out)


def make_schedule(mesh: Mesh, nq: int, *, ref_axis: str = "ref",
                  dp_axis: Optional[str] = None,
                  n_micro: Optional[int] = None) -> PipelineSchedule:
    """Resolve mesh axes and pick the microbatch layout for ``nq`` queries.

    Default ``n_micro`` fills the systolic pipeline (up to ``n_mp``
    microbatches per dp row) without exceeding the query count. An
    explicit ``n_micro`` is validated: every dp row must get at least one
    real query per microbatch slot, otherwise the schedule would be pure
    padding — reject loudly instead of silently clamping.
    """
    dpax, mpax = pipeline_axes(mesh, ref_axis=ref_axis, dp_axis=dp_axis)
    n_dp = mesh.shape[dpax] if dpax is not None else 1
    n_mp = mesh.shape[mpax]
    if n_micro is None:
        n_micro = max(1, min(n_mp, -(-max(1, nq) // n_dp)))
    else:
        n_micro = int(n_micro)
        if n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {n_micro}")
        if n_dp * n_micro > max(1, nq):
            raise ValueError(
                f"n_micro={n_micro} exceeds the padded batch: {n_dp} dp "
                f"row(s) x {n_micro} microbatches > {nq} queries, so at "
                f"least one microbatch slot would be pure padding; lower "
                f"n_micro or leave it None")
    mb = max(1, -(-nq // (n_dp * n_micro)))
    return PipelineSchedule(dpax, mpax, n_dp, n_mp, n_micro, mb, nq)


def _segment_layout(m: int, n_mp: int, chunk: int):
    """Per-device reference segment length (a chunk multiple) + the chunk."""
    seg = max(1, -(-m // n_mp))
    chunk = min(chunk, seg)
    seg = _ceil_to(seg, chunk)
    return seg, chunk


# ---------------------------------------------------------------------------
# Bounded pipeline cache (keyed on mesh fingerprints, not live Mesh objects)
# ---------------------------------------------------------------------------

_PIPELINE_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
PIPELINE_CACHE_MAX = 64


def _mesh_key(mesh: Mesh) -> tuple:
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def clear_pipeline_cache() -> None:
    """Drop every cached compiled pipeline (tests; device topology change)."""
    _PIPELINE_CACHE.clear()


def _cache_size() -> int:
    """Number of live compiled pipelines (the ``_cache_size()`` pattern)."""
    return len(_PIPELINE_CACHE)


# ---------------------------------------------------------------------------
# THE pipeline builder — the only systolic tick body in the sharded layer
# ---------------------------------------------------------------------------

def build_pipeline(mesh: Mesh, *, dp_axis: Optional[str], mp_axis: str,
                   metric: str, chunk: int, n_micro: int,
                   top_k: Optional[int] = None, excl_zone=0,
                   excl_span: bool = False, track_start: bool = False,
                   entry: str = "fresh", harvest: str = "result"):
    """Build (or fetch) the jitted shard-mapped systolic pipeline.

    One parameterized body serves every sharded path:

      * ``entry='fresh'``  — each microbatch starts from the fresh sDTW
        carry init (the batch paths);
        ``entry='carry'``  — device 0 enters each microbatch from stacked
        caller-provided carries (the streaming feed).
      * ``harvest='result'`` — emit only the final result per tick (the
        running best, or the top-K heap triple);
        ``harvest='carry'``  — emit the full carry tuple exiting the last
        device (boundary column, start lane, best, heap) so the caller can
        keep feeding macro-chunks.

    With ``top_k`` set, the per-microbatch match heap (top-K distances,
    global end positions, and start positions, see ``repro.core.topk``)
    rides the systolic carry exactly like the boundary column — which
    itself gains the start-pointer lane so spans survive the inter-device
    hand-off: each device folds the candidates of its own reference
    segment into the heap it received from the left neighbour, so the heap
    exiting the last device is already the merged cross-shard top-K.

    With a dp axis, microbatch slots (and carries) arrive sharded over it;
    each dp row runs the schedule on its local (n_micro, mb, ...) slice
    and the dp-sharded out-spec stitches rows back — the dp harvest is
    free.
    """
    if entry not in ("fresh", "carry"):
        raise ValueError(f"entry must be 'fresh' or 'carry', got {entry!r}")
    if harvest not in ("result", "carry"):
        raise ValueError(f"harvest must be 'result' or 'carry', got "
                         f"{harvest!r}")
    key = (_mesh_key(mesh), dp_axis, mp_axis, metric, chunk, n_micro,
           top_k, excl_zone, excl_span, track_start, entry, harvest)
    hit = _PIPELINE_CACHE.get(key)
    if hit is not None:
        _PIPELINE_CACHE.move_to_end(key)
        return hit

    n_mp = mesh.shape[mp_axis]
    perm = neighbor_perm(n_mp)
    ticks = n_micro + n_mp - 1
    with_carry = entry == "carry"

    def body(r_shard, q_micro, qlen_micro, lo_micro, hi_micro, m_total,
             j0_base, *carry_args):
        # r_shard: (1, seg) this device's reference segment, replicated
        # over dp. q_micro (and carry leaves): dp-local (n_micro, mb, ...).
        carry_in = carry_args[0] if with_carry else None
        d = lax.axis_index(mp_axis)
        seg = r_shard.shape[1]
        j0 = j0_base + d * seg
        mb, n = q_micro.shape[1], q_micro.shape[2]
        acc = accum_dtype(jnp.result_type(q_micro, r_shard))
        fresh = sdtw_carry_init(mb, n, acc,
                                track_start=top_k is not None and
                                track_start)
        if top_k is not None:
            fresh = fresh + topk_init(mb, top_k, acc)

        def tick(carry, t):
            mb_idx = jnp.clip(t - d, 0, n_micro - 1)
            pick = lambda x: lax.dynamic_index_in_dim(x, mb_idx,
                                                      keepdims=False)
            q, ql = pick(q_micro), pick(qlen_micro)
            lo, hi = pick(lo_micro), pick(hi_micro)
            # Device 0 always *enters* a microbatch: from the fresh init
            # (batch) or from the caller's stacked carry (feed). The
            # others continue from whatever the left neighbour handed
            # over.
            own = jax.tree.map(pick, carry_in) if with_carry else fresh
            cin = jax.tree.map(
                lambda f, c: jnp.where(d == 0, f, c.astype(f.dtype)),
                own, carry)
            if top_k is not None:
                ez = (default_excl_zone(ql) if excl_zone is None
                      else jnp.full(ql.shape, excl_zone, jnp.int32))
                cout = sdtw_segment_topk(q, r_shard[0], ql, cin, j0,
                                         m_total, metric, chunk, lo, hi,
                                         top_k, ez, excl_span, track_start)
            else:
                cout = sdtw_segment(q, r_shard[0], ql, cin, j0, m_total,
                                    metric, chunk, lo, hi)
            if harvest == "carry":
                emit = cout                        # full carry hand-off
            elif top_k is not None:
                emit = cout[-3:]                   # heap: d, ends, starts
            else:
                emit = cout[1]                     # running best
            nxt = jax.tree.map(lambda x: lax.ppermute(x, mp_axis, perm),
                               cout)
            return nxt, emit

        # The scan init never reaches a harvested value: device 0 always
        # swaps in its entry carry, and downstream devices only consume
        # ppermute'd outputs — ``fresh`` is just a correctly-shaped seed.
        _, outs = lax.scan(tick, fresh, jnp.arange(ticks))  # (ticks, mb,…)
        # The last device finishes microbatch μ at tick μ + n_mp - 1; only
        # its in-window ticks carry fully merged results.
        return psum_harvest(outs, mp_axis, n_mp, n_micro)

    mspec = P(dp_axis) if dp_axis is not None else P()
    in_specs = (P(None, mp_axis), mspec, mspec, mspec, mspec, P(), P())
    if with_carry:
        in_specs = in_specs + (mspec,)             # pytree prefix
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=mspec,                           # pytree prefix
        check_vma=False)
    fn = jax.jit(mapped)
    _PIPELINE_CACHE[key] = fn
    while len(_PIPELINE_CACHE) > PIPELINE_CACHE_MAX:
        _PIPELINE_CACHE.popitem(last=False)
    return fn


# ---------------------------------------------------------------------------
# Entry points — thin instantiations of the one builder
# ---------------------------------------------------------------------------

def sdtw_sharded_feed(r_macro, q_micro, qlen_micro, lo_micro, hi_micro,
                      carry, j0: int, m_total: int, *, mesh: Mesh,
                      axis: str = "ref", dp_axis: Optional[str] = None,
                      chunk: int, metric: str,
                      top_k=None, excl_zone=None, excl_span: bool = False,
                      track_start: bool = False):
    """Advance stacked per-microbatch carries by one sharded macro-chunk.

    ``r_macro`` is (n_mp * seg,) with seg a multiple of ``chunk``; systolic
    device d processes global columns ``[j0 + d*seg, j0 + (d+1)*seg)``.
    ``carry`` leaves are (slots, mb, ...) with slots = n_dp * n_micro, as
    produced by a previous feed (or the caller's stacked fresh init); the
    return value is the updated carry in the same layout. ``m_total``
    masks columns past the true stream end, so a right-padded final
    macro-chunk still folds correct distances/heaps (its exiting boundary
    column is garbage — a padded feed must be the last, which is why the
    sharded session treats a tail flush as terminal)."""
    dpax, mpax = pipeline_axes(mesh, ref_axis=axis, dp_axis=dp_axis)
    n_dp = mesh.shape[dpax] if dpax is not None else 1
    n_mp = mesh.shape[mpax]
    slots = q_micro.shape[0]
    if slots % n_dp:
        raise ValueError(f"{slots} microbatch slots do not split over "
                         f"{n_dp} dp rows")
    n_micro = slots // n_dp
    seg = r_macro.shape[0] // n_mp
    if seg * n_mp != r_macro.shape[0] or seg % chunk:
        raise ValueError(
            f"macro-chunk of {r_macro.shape[0]} does not split into "
            f"{n_mp} devices x multiple of chunk={chunk}")
    run = build_pipeline(mesh, dp_axis=dpax, mp_axis=mpax, metric=metric,
                         chunk=chunk, n_micro=n_micro, top_k=top_k,
                         excl_zone=excl_zone, excl_span=excl_span,
                         track_start=track_start,
                         entry="carry", harvest="carry")
    return run(r_macro.reshape(1, n_mp * seg), q_micro, qlen_micro,
               lo_micro, hi_micro, jnp.int32(m_total), jnp.int32(j0),
               carry)


def sdtw_sharded(queries, reference, qlens=None, *, metric: str = "abs_diff",
                 mesh: Optional[Mesh] = None, axis: str = "ref",
                 dp_axis: Optional[str] = None,
                 chunk: int = 8192, n_micro: Optional[int] = None,
                 excl_lo=None, excl_hi=None,
                 top_k: Optional[int] = None,
                 excl_zone: Optional[int] = None,
                 return_positions: bool = False,
                 return_spans: bool = False, excl_mode: str = "end"):
    """Batched sDTW with the reference sharded across the mesh.

    queries (nq, N), reference (M,) → (nq,) distances, matching the
    single-device engine bit-for-bit for int32 inputs — across every
    (dp, mp) factorization and every valid ``n_micro``.

    On a 1-D mesh the whole device set forms the systolic pipeline; on a
    2-D (dp, mp) mesh each dp row runs the pipeline over its shard of the
    query microbatches with the reference replicated within the row (build
    one with ``repro.distributed.get_mesh``).

    ``top_k=k`` returns ``(dists (nq, k), positions (nq, k))`` — the match
    heap travels with the microbatch through the device pipeline (the same
    ppermute that hands over the boundary column), so the cross-shard merge
    costs no extra collective; positions are global reference indices.
    ``return_positions=True`` alone returns the top-1 pair;
    ``return_spans=True`` returns ``(dists, starts, ends)`` — the
    start-pointer lane crosses devices inside the same ppermute'd carry.
    ``excl_mode='span'`` keys heap suppression on span overlap.
    """
    if mesh is None:
        mesh = default_mesh(axis)
    queries = jnp.asarray(queries)
    reference = jnp.asarray(reference)
    nq, n = queries.shape
    m = reference.shape[0]
    if qlens is None:
        qlens = jnp.full((nq,), n, jnp.int32)
    if excl_lo is None:
        excl_lo = jnp.full((nq,), -1, jnp.int32)
    if excl_hi is None:
        excl_hi = jnp.full((nq,), -1, jnp.int32)

    sched = make_schedule(mesh, nq, ref_axis=axis, dp_axis=dp_axis,
                          n_micro=n_micro)
    seg, chunk = _segment_layout(m, sched.n_mp, chunk)
    r_pad = jnp.pad(reference, (0, seg * sched.n_mp - m)).reshape(
        1, seg * sched.n_mp)

    wants_pair = top_k is not None or return_positions or return_spans
    kk = (1 if top_k is None else top_k) if wants_pair else None
    if excl_zone is not None and np.ndim(excl_zone) != 0:
        # The zone is baked into the cached pipeline build; per-query
        # arrays (which sdtw_chunked accepts) would need to ride the
        # traced inputs — reject loudly rather than crash in int().
        raise ValueError("sdtw_sharded takes a scalar excl_zone (or None "
                         "for the per-query default); per-query zone "
                         "arrays are only supported on the single-device "
                         "chunked path")
    # zone is unused by the plain pipeline — pin it so non-top-K calls
    # share one pipeline cache entry. None = derive per query in the body
    # (half the true query length — or 0 in span mode — matching the
    # single-device default).
    if kk is None:
        zone = 0
    elif excl_zone is not None:
        zone = int(excl_zone)
    else:
        zone = None if excl_mode == "end" else 0
    # The start lane crosses the ppermute carry only when starts are
    # consumed (spans requested or span-overlap suppression).
    track = return_spans or excl_mode == "span"
    run = build_pipeline(mesh, dp_axis=sched.dp_axis, mp_axis=sched.mp_axis,
                         metric=metric, chunk=chunk, n_micro=sched.n_micro,
                         top_k=kk, excl_zone=zone,
                         excl_span=excl_mode == "span", track_start=track,
                         entry="fresh", harvest="result")
    outs = run(r_pad, sched.pack(queries),
               sched.pack(qlens, fill=1),
               sched.pack(excl_lo, fill=-1), sched.pack(excl_hi, fill=-1),
               jnp.int32(m), jnp.int32(0))
    if not wants_pair:
        return sched.unpack(outs)
    dists, poss, starts = sched.unpack(outs)
    if top_k is None:                       # top-1, unstacked
        if return_spans:
            return dists[:, 0], starts[:, 0], poss[:, 0]
        return dists[:, 0], poss[:, 0]
    if return_spans:
        return dists, starts, poss
    return dists, poss
