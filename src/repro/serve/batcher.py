"""Microbatch coalescing: many client requests → few engine dispatches.

A drained window of admitted requests is grouped by
``SdtwRequest.coalesce_key()`` (everything that selects a compiled
executable or changes per-query semantics) plus the reference identity
and the query dtype. Each group becomes ONE merged ragged engine call:
every client's queries are trimmed to true length and concatenated into
one ragged list, so the engine's existing power-of-two bucketing yields
one DP dispatch per bucket per window — serving reuses the exact
amortization machinery of the offline path instead of duplicating it.

Within a group, **identical** requests deduplicate: the dedup key is
``(ref_fingerprint, query fingerprint, coalesce_key)`` — the group key
already pins the first and last components, and the query fingerprint
hashes each trimmed query's shape/dtype/bytes — so N concurrent clients
asking the same question cost one engine call and share one result
object (the same sliced arrays, bitwise-trivially; pinned by tests).

Correctness contract (pinned by ``tests/test_serve.py``):

  * ``op='sdtw'`` — the DP is per-query independent and the padded
    columns are masked by ``qlens``, so the merged call is **bitwise**
    identical (int32) to each client calling ``engine.sdtw`` alone.
  * ``op='search_topk'`` — the LB-cascade thresholds are batch-shared
    (a chunk is pruned only when *no* query in the batch can improve),
    so the merged call is bitwise identical to one offline *batched*
    ``search_topk`` over the same queries; top-1 distances additionally
    match the per-client calls exactly (the cascade never prunes a true
    winner).

A group of one request dispatches the request unchanged — zero
repacking, trivially identical to the offline call.

Delivery is cancellation-safe: a client that cancelled its future
before delivery is skipped via ``set_running_or_notify_cancel()`` (and
counted in telemetry) without disturbing the other members — a
cancelled future can no longer poison its group.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.request import SdtwRequest

from .telemetry import RequestTrace, request_ids


@dataclasses.dataclass
class Pending:
    """One admitted request waiting for dispatch."""
    request: SdtwRequest
    future: object               # concurrent.futures.Future
    trace: RequestTrace
    single: bool = False         # client passed one 1-D query
    entries: list = None         # true-length 1-D query arrays
    dupes: list = None           # identical requests sharing this
                                 # member's engine call and result


def ref_fingerprint(req: SdtwRequest):
    """Reference identity for grouping: the user's stable ``ref_key``
    when given (callers assert equal keys mean equal content — same
    contract as the envelope cache), else object identity; shape/dtype
    folded in so a stale key can never merge mismatched references."""
    ref = np.asarray(req.reference)
    base = req.ref_key if req.ref_key is not None else ("id",
                                                        id(req.reference))
    return (base, ref.shape, str(ref.dtype))


def query_entries(req: SdtwRequest):
    """Flatten a request's queries into true-length 1-D arrays.

    Returns ``(entries, single)`` — padded 2-D input is trimmed per
    ``qlens`` (the engine masks padded columns by qlens, so repacking
    is bitwise-invariant; the repo's ragged differential tests pin
    this)."""
    q = req.queries
    if isinstance(q, (list, tuple)):
        return [np.asarray(x) for x in q], False
    arr = np.asarray(q)
    if arr.ndim == 1:
        return [arr], True
    if req.qlens is not None:
        lens = np.asarray(req.qlens).astype(int)
        return [arr[i, :lens[i]] for i in range(arr.shape[0])], False
    return [arr[i] for i in range(arr.shape[0])], False


def query_fingerprint(p: Pending):
    """Content hash of a request's trimmed queries — the in-window dedup
    key component. Two requests with equal group keys and equal query
    fingerprints would run the byte-identical engine call, so one runs
    and both share its result. ``single`` is folded in because a 1-D
    client's slice unwraps to a scalar shape."""
    h = hashlib.blake2b(digest_size=16)
    h.update(b"1" if p.single else b"0")
    for e in p.entries:
        arr = np.ascontiguousarray(e)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.digest()


def group_key(req: SdtwRequest):
    """Full coalescing key: semantic key × reference × query dtype (the
    accumulator dtype depends on both operand dtypes, so mixing query
    dtypes in one batch would change every client's result type).
    Per-query exclusion *arrays* are sized to one request's batch and
    cannot be concatenated semantically — such requests never coalesce
    at all (unique key), even when two clients share the array object."""
    entries, _ = query_entries(req)
    qdtype = str(np.result_type(*entries)) if entries else "none"
    per_query = tuple(np.ndim(v) != 0 for v in
                      (req.excl_zone, req.excl_lo, req.excl_hi)
                      if v is not None)
    solo = (id(req),) if any(per_query) else ()
    return req.coalesce_key(ref_id=ref_fingerprint(req)) + (qdtype,) + solo


def group_window(pending: list, *, dedup: bool = True) -> list:
    """Partition a drained window into coalescable groups (stable
    order). With ``dedup`` (the default), identical requests within a
    group collapse onto the first-submitted member's ``dupes`` list —
    only the surviving members contribute query entries to the merged
    call."""
    groups: dict = {}
    for p in pending:
        p.entries, p.single = query_entries(p.request)
        p.dupes = []
        groups.setdefault(group_key(p.request), []).append(p)
    if not dedup:
        return list(groups.values())
    out = []
    for members in groups.values():
        primaries: dict = {}
        kept = []
        for p in members:
            fp = query_fingerprint(p)
            prim = primaries.get(fp)
            if prim is None:
                primaries[fp] = p
                kept.append(p)
            else:
                prim.dupes.append(p)
        out.append(kept)
    return out


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def group_shape(group: list):
    """Proxy for the compiled executable a merged group will exercise:
    the pow-2 bucket its ragged batch lands in (query count and length
    are both bucketed by the engine) plus op and reference shape/dtype.
    The ``DevicePool`` keys executable affinity on this — two groups
    with equal shapes hit the same jit cache entry on a device that has
    run either, so routing them together avoids a recompile. The key is
    a heuristic: an imprecise match only costs one extra compile, never
    correctness (results are device-invariant, pinned by tests)."""
    p0 = group[0]
    for p in group:
        if p.entries is None:
            p.entries, p.single = query_entries(p.request)
    total = sum(len(p.entries) for p in group)
    qmax = max((e.shape[-1] for p in group for e in p.entries), default=0)
    ref = np.asarray(p0.request.reference)
    return (p0.request.op, _pow2(total), _pow2(qmax), ref.shape,
            str(ref.dtype))


def group_members(group: list):
    """Every client request answered by this group's engine call —
    the surviving members plus their deduplicated twins."""
    for p in group:
        yield p
        yield from (p.dupes or ())


def _slice_result(res, i0: int, i1: int, single: bool):
    """Cut one client's rows out of a merged result (array, tuple of
    arrays, or SearchResult — every payload's leading axis is nq)."""
    if isinstance(res, tuple):
        return tuple(_slice_result(r, i0, i1, single) for r in res)
    if hasattr(res, "distances"):        # SearchResult: slice the payload,
        return dataclasses.replace(      # share the batch-level telemetry
            res,
            distances=_slice_result(res.distances, i0, i1, single),
            positions=_slice_result(res.positions, i0, i1, single),
            starts=_slice_result(res.starts, i0, i1, single))
    out = res[i0:i1]
    return out[0] if single else out


def _deliver_one(p: Pending, result, exc, telemetry):
    """Resolve one member future, tolerating client cancellation and
    already-resolved futures (a cancelled/raced member must not disturb
    its groupmates)."""
    fut = p.future
    if fut.cancelled():
        if telemetry is not None:
            telemetry.record_cancelled()
        return
    if fut.done():
        return                          # answered elsewhere (close race)
    if not fut.set_running_or_notify_cancel():
        if telemetry is not None:       # cancelled between the checks
            telemetry.record_cancelled()
        return
    p.trace.mark_complete(error=exc is not None)
    if telemetry is not None:
        telemetry.record_complete(p.trace)
    if exc is not None:
        fut.set_exception(exc)
    else:
        fut.set_result(result)


def fail_group(group: list, exc, telemetry=None):
    """Answer every not-yet-resolved member future with ``exc``."""
    for p in group_members(group):
        _deliver_one(p, None, exc, telemetry)


def execute_group(group: list, telemetry=None):
    """Run one coalesced group and deliver every client future.

    Never raises: an execution error is propagated into every member
    future (the admission contract — admitted requests are always
    answered). Deduplicated twins receive the *same* result object as
    their surviving member. Each trace is completed and recorded
    *before* its future resolves, so a client that has its result is
    guaranteed to already be counted in the stats snapshot. Delivery
    runs in the ``serve.deliver`` span."""
    members = list(group_members(group))
    for p in members:
        p.trace.mark_dispatch()

    def deliver(p, result=None, exc=None):
        for member in (p, *(p.dupes or ())):
            _deliver_one(member, result, exc, telemetry)

    try:
        if len(group) == 1:
            res = group[0].request.run()
        else:
            merged = [e for p in group for e in p.entries]
            base = group[0].request
            res = dataclasses.replace(base, queries=merged, qlens=None).run()
        for p in members:
            p.trace.mark_launched()
        with TraceAnnotation("serve.deliver",
                             req=request_ids(p.trace for p in members)):
            if len(group) == 1:
                deliver(group[0], res)
                return
            i0 = 0
            for p in group:
                i1 = i0 + len(p.entries)
                deliver(p, _slice_result(res, i0, i1, p.single))
                i0 = i1
    except Exception as exc:                           # noqa: BLE001
        fail_group(group, exc, telemetry=telemetry)
