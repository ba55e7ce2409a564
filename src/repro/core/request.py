"""Unified request objects — ONE argument surface for every sDTW front door.

Before this module existed, the ~15 overlapping keyword arguments of
``engine.sdtw``, ``engine.stream`` and ``repro.search.search_topk`` were
triple-duplicated, each front door re-implementing its own validation
with slowly drifting defaults, docstrings and error messages. Now each
front door is a *thin shim* that builds a frozen request dataclass and
funnels it through one shared validator/dispatcher:

  * ``SdtwRequest``   — an offline call: ``op='sdtw'`` (the engine) or
    ``op='search_topk'`` (the pruned search layer). ``request.run()``
    validates, normalizes and dispatches; it is byte-for-byte the same
    code path as the keyword front doors, so kwargs callers and serve-
    tier tenants (``repro.serve``) hit identical argument semantics.
  * ``StreamRequest`` — an online session: ``request.open()`` returns
    the ``StreamSession`` / ``ShardedStreamSession`` that
    ``engine.stream`` would have built.

The request object is also the serve tier's *queue element*: an
admission-controlled router (``repro.serve``) enqueues validated
requests and coalesces the ones that share a ``coalesce_key()`` into one
batched engine call per ragged power-of-two bucket — the same
bucketing/compile-cache key derivation the engine itself uses, defined
here exactly once.

Argument semantics documented once (the front-door docstrings point
here):

  * ``excl_zone`` — top-K suppression radius between reported matches.
    ``None`` derives the default *per query*: half of each query's true
    length with ``excl_mode='end'`` (the matrix-profile convention), 0
    with ``excl_mode='span'`` (span-overlap suppression already keeps
    events sample-disjoint). A scalar applies to every query. A
    per-query ``(nq,)`` array is honoured by the single-device chunked
    path only — the sharded driver and the search layer take scalars
    (the search layer historically *silently truncated* arrays via
    ``int()``; the shared validator now rejects them loudly there).
  * ``excl_lo``/``excl_hi`` — banned reference column range (self-join
    exclusion); must be given together on every front door (a one-sided
    zone would silently ban nothing).
  * ``top_k``/``k`` — matches per query; positive int. The search front
    door spells it ``k``; both land in ``SdtwRequest.top_k``.

Validation error messages are preserved byte-for-byte from the pre-
request front doors (tests pin them); where two front doors historically
used *different* words for the same rejection, the shared validator
keeps each op's message under one roof instead of quietly changing a
public contract — the drift is now visible in one file instead of three.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

#: Engine execution regimes (``SdtwRequest.impl``).
IMPLS = ("auto", "rowscan", "wavefront", "pallas", "chunked", "sharded")
#: Streaming session regimes (``StreamRequest.impl``).
STREAM_IMPLS = ("auto", "rowscan", "pallas", "sharded")
#: Top-K suppression modes.
EXCL_MODES = ("end", "span")
#: Search-layer DP backends (``SdtwRequest.engine_impl``).
SEARCH_ENGINE_IMPLS = ("auto", "rowscan", "pallas")
#: Request operations.
OPS = ("sdtw", "search_topk")
#: Autotuning modes (``SdtwRequest.tune``) — see ``repro.tune``.
TUNE_MODES = ("model", "measure", "off")


def resolve_mesh(mesh, mesh_shape):
    """``mesh_shape=`` builds the (dp, mp) mesh via the distributed layer."""
    if mesh_shape is None:
        return mesh
    if mesh is not None:
        raise ValueError("pass either mesh= (a prebuilt jax Mesh) or "
                         "mesh_shape= (built for you), not both")
    from repro.distributed.sharding import get_mesh
    return get_mesh(mesh_shape)


def _check_forced_impl(impl: str, *, mesh, chunk, top_k):
    """Explicit precedence for forced impls: reject contradictory args
    instead of silently ignoring them."""
    if impl in ("rowscan", "wavefront"):
        if mesh is not None:
            raise ValueError(
                f"impl={impl!r} is an in-core path but mesh= requests the "
                "sharded driver; drop mesh= or use impl='sharded'/'auto'")
        if chunk is not None:
            raise ValueError(
                f"impl={impl!r} runs in-core and would ignore chunk=; drop "
                "chunk= or use impl='chunked'/'pallas' for streaming")
        if top_k is not None:
            raise ValueError(
                f"impl={impl!r} does not carry a top-K heap; top_k= runs on "
                "the chunked/sharded streaming paths (impl='auto' routes it)")
    elif impl == "pallas":
        if mesh is not None:
            raise ValueError(
                "impl='pallas' is single-device; drop mesh= or use "
                "impl='sharded'/'auto'")
        if top_k is not None:
            raise ValueError(
                "impl='pallas' reports the single best match "
                "(return_positions/return_spans); offline top_k= runs on "
                "the chunked/sharded streaming paths — the kernel's "
                "last-row capture serves top-K via repro.search "
                "(engine_impl='pallas') and streaming sessions")
    elif impl == "chunked" and mesh is not None:
        raise ValueError(
            "impl='chunked' is single-device; drop mesh= or use "
            "impl='sharded'/'auto'")


def _check_sharded_args(*, mesh, impl, n_micro, excl_zone, top_k,
                        return_positions):
    """Loud rejection of options the sharded path cannot honour — instead
    of silently mishandling them deep in the driver."""
    sharded = mesh is not None or impl == "sharded"
    if n_micro is not None and not sharded:
        raise ValueError("n_micro= schedules the sharded systolic "
                         "pipeline; pass mesh=/mesh_shape= (or "
                         "impl='sharded') or drop n_micro=")
    if not sharded:
        return
    if excl_zone is not None and np.ndim(excl_zone) != 0:
        raise ValueError("the sharded driver takes a scalar excl_zone (or "
                         "None for the per-query default); per-query zone "
                         "arrays run on the single-device chunked path "
                         "(drop mesh=)")
    if return_positions and top_k is not None:
        raise ValueError("top_k= already returns (dists, positions) on "
                         "the sharded driver; return_positions=True adds "
                         "nothing there — drop it (or use return_spans=)")


def _check_common(req, *, op_word: str = "top_k"):
    """Checks every offline op shares (messages pinned by the test
    matrix). ``op_word`` keeps the historically different spelling of the
    top-K argument per front door ('top_k' for the engine, 'k' for the
    search layer)."""
    if (req.excl_lo is None) != (req.excl_hi is None):
        raise ValueError("excl_lo and excl_hi must be given together "
                         "(a one-sided zone would silently ban nothing)")
    if req.top_k is not None and (not isinstance(req.top_k, int)
                                  or req.top_k < 1):
        raise ValueError(f"{op_word} must be a positive int, got "
                         f"{req.top_k!r}")
    if isinstance(req.queries, (list, tuple)) and req.qlens is not None:
        raise ValueError("qlens is implied by ragged (list) queries")


def _mesh_fingerprint(mesh):
    """Hashable identity of a mesh for compile-cache / coalesce keys —
    axis names + device ids, as the sharded pipeline cache keys it."""
    if mesh is None:
        return None
    try:
        return (tuple(mesh.axis_names),
                tuple(int(d.id) for d in np.ravel(mesh.devices)))
    except AttributeError:                     # test doubles / stubs
        return ("mesh", id(mesh))


def _scalar_or_id(val):
    """Coalesce-key component for a possibly-array argument: scalar
    values coalesce by value, arrays never coalesce across requests."""
    if val is None:
        return None
    if np.ndim(val) == 0:
        return ("s", float(np.asarray(val)))
    return ("a", id(val))


def _reject_unknown(cls, kwargs):
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(kwargs) - fields)
    if unknown:
        raise ValueError(
            f"unknown {cls.__name__} argument(s) {unknown}; valid "
            f"arguments are {sorted(fields)}")


@dataclasses.dataclass(frozen=True)
class SdtwRequest:
    """One offline sDTW call, as data.

    ``op='sdtw'`` runs the engine (``repro.core.engine``);
    ``op='search_topk'`` runs the pruned search layer (``repro.search``).
    The fields are exactly the union of the two front doors' keyword
    arguments — see their docstrings (and the module docstring above for
    the semantics shared verbatim between them). Search-only fields
    (``prune``, ``span_cap``, ``normalize``, ``cache``, ``ref_key``,
    ``engine_impl``) are ignored by ``op='sdtw'``.

    Frozen: a request is immutable after construction; derive variants
    with ``dataclasses.replace``. ``run()`` validates, normalizes and
    dispatches — the same path every keyword front door takes.
    """
    queries: Any = None
    reference: Any = None
    qlens: Any = None
    metric: str = "abs_diff"
    impl: str = "auto"
    chunk: Optional[int] = None
    excl_lo: Any = None
    excl_hi: Any = None
    mesh: Any = None
    mesh_shape: Any = None
    ref_axis: str = "ref"
    n_micro: Optional[int] = None
    top_k: Optional[int] = None
    return_positions: bool = False
    return_spans: bool = False
    excl_zone: Any = None
    excl_mode: str = "end"
    block_q: Optional[int] = None
    block_m: Optional[int] = None
    #: Autotuning mode: 'model' (cost model + tuning table fill unset
    #: knobs, the default), 'measure' (refine the bucket on-device once
    #: per process before dispatch), 'off' (legacy hand-tuned constants).
    #: Bitwise-safe: int32 results are invariant to it.
    tune: str = "model"
    #: Return ``(result, repro.tune.DispatchDecision)`` instead of the
    #: bare result. Rejected by the serve tier (a coalesced batch has no
    #: single per-request decision) and for ragged lists.
    explain: bool = False
    op: str = "sdtw"
    # --- serve-tier-only -------------------------------------------------
    # Scheduling metadata for the admission queue (``repro.serve``):
    # higher ``priority`` drains sooner (aging keeps lower classes
    # starvation-free), ``tenant`` scopes per-tenant quotas. Both are
    # ignored by ``run()`` and deliberately excluded from
    # ``coalesce_key()`` — requests from different tenants/priorities
    # still share one merged engine call once drained into a window.
    priority: int = 0
    tenant: Any = None
    # --- search_topk-only ------------------------------------------------
    prune: bool = True
    span_cap: Optional[int] = None
    normalize: bool = False
    cache: Any = None
    ref_key: Any = None
    engine_impl: str = "auto"

    @classmethod
    def from_kwargs(cls, **kwargs) -> "SdtwRequest":
        """Build a request from a kwargs dict, rejecting unknown keys
        loudly (the dict-driven serve tier's entry point — a typo'd
        argument must not be silently dropped)."""
        _reject_unknown(cls, kwargs)
        return cls(**kwargs)

    # ------------------------------------------------------------------
    # the shared validator
    # ------------------------------------------------------------------

    def validate(self) -> "SdtwRequest":
        """Run every front-door check (shape-independent ones; the
        dispatcher still owns shape-dependent rejections such as
        pallas × exclusion zones after ``impl='auto'`` resolution).
        Returns ``self`` so calls chain."""
        if self.op not in OPS:
            raise ValueError(f"op must be one of {OPS}, got {self.op!r}")
        if not isinstance(self.priority, int) \
                or isinstance(self.priority, bool):
            raise ValueError(f"priority must be an int (higher drains "
                             f"sooner), got {self.priority!r}")
        try:
            hash(self.tenant)
        except TypeError:
            raise ValueError(f"tenant must be hashable (it keys per-tenant "
                             f"quotas), got {type(self.tenant).__name__}") \
                from None
        if self.tune not in TUNE_MODES:
            raise ValueError(f"tune must be one of {TUNE_MODES}, got "
                             f"{self.tune!r}")
        if self.op == "search_topk":
            return self._validate_search()
        if self.impl not in IMPLS:
            raise ValueError(f"impl must be one of {IMPLS}, got "
                             f"{self.impl!r}")
        if self.excl_mode not in EXCL_MODES:
            raise ValueError(f"excl_mode must be one of {EXCL_MODES}, got "
                             f"{self.excl_mode!r}")
        _check_common(self, op_word="top_k")
        if self.excl_mode == "span" and self.top_k is None:
            raise ValueError("excl_mode='span' only affects top-K "
                             "suppression; pass top_k= (k=1 selection "
                             "never suppresses)")
        mesh = resolve_mesh(self.mesh, self.mesh_shape)
        _check_forced_impl(self.impl, mesh=mesh, chunk=self.chunk,
                           top_k=self.top_k)
        _check_sharded_args(mesh=mesh, impl=self.impl, n_micro=self.n_micro,
                            excl_zone=self.excl_zone, top_k=self.top_k,
                            return_positions=self.return_positions)
        return self

    def _validate_search(self) -> "SdtwRequest":
        # The search front door spells top_k as ``k`` and keeps its own
        # historical message wording — pinned by the existing test matrix.
        if self.top_k is None or not isinstance(self.top_k, int) \
                or self.top_k < 1:
            raise ValueError(f"k must be a positive int, got {self.top_k!r}")
        if self.excl_mode not in EXCL_MODES:
            raise ValueError(f"excl_mode must be 'end' or 'span', got "
                             f"{self.excl_mode!r}")
        if (self.excl_lo is None) != (self.excl_hi is None):
            raise ValueError("excl_lo and excl_hi must be given together "
                             "(a one-sided zone would silently ban nothing)")
        if self.excl_zone is not None and np.ndim(self.excl_zone) != 0:
            raise ValueError("search_topk takes a scalar excl_zone (or "
                             "None for the per-query default); per-query "
                             "zone arrays run on engine.sdtw's chunked "
                             "path")
        mesh = resolve_mesh(self.mesh, self.mesh_shape)
        if mesh is not None and self.prune:
            raise ValueError("mesh= runs the sharded engine over every "
                             "chunk; pass prune=False explicitly (the LB "
                             "cascade is single-process)")
        if self.engine_impl not in SEARCH_ENGINE_IMPLS:
            raise ValueError(f"engine_impl must be 'auto', 'rowscan' or "
                             f"'pallas', got {self.engine_impl!r}")
        has_excl = self.excl_lo is not None or self.excl_hi is not None
        if self.engine_impl == "pallas" and has_excl:
            raise ValueError("the pallas kernel does not support per-query "
                             "exclusion zones; use engine_impl='rowscan'")
        if isinstance(self.queries, (list, tuple)) and self.qlens is not None:
            raise ValueError("qlens is implied by ragged (list) queries")
        return self

    def normalized(self) -> "SdtwRequest":
        """Validate and return the canonical form: ``mesh_shape`` resolved
        to a concrete mesh (so equal-meaning requests compare equal where
        it matters — dispatch and coalescing see one field, not two)."""
        self.validate()
        if self.mesh_shape is None:
            return self
        return dataclasses.replace(
            self, mesh=resolve_mesh(self.mesh, self.mesh_shape),
            mesh_shape=None)

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def run(self):
        """Validate, normalize and execute — byte-identical to calling the
        keyword front door (``engine.sdtw`` / ``search_topk``), because
        the front doors are shims over this very method."""
        req = self.normalized()
        if req.op == "search_topk":
            from repro.search import search as search_mod
            return search_mod._execute_search(req)
        from repro.core import engine
        return engine._execute_sdtw(req)

    # ------------------------------------------------------------------
    # serve-tier key derivation (bucketing / compile cache / coalescing)
    # ------------------------------------------------------------------

    def coalesce_key(self, ref_id=None):
        """Hashable key under which requests may share one batched engine
        call: everything that selects a compiled executable or changes
        per-query semantics, *except* the queries themselves. Two
        requests with equal keys (and the same reference, folded in via
        ``ref_id``) can be concatenated into one ragged batch — the
        engine's power-of-two bucketing then guarantees one dispatch per
        bucket per microbatch window, and per-query independence of the
        DP guarantees bitwise-identical answers to per-client calls.

        Per-query exclusion arrays (``excl_lo/hi/zone`` as arrays) key by
        object identity, i.e. such requests never coalesce with others.
        """
        return (self.op, self.metric, self.impl, self.chunk,
                self.top_k, self.return_positions, self.return_spans,
                self.excl_mode, self.block_q, self.block_m, self.tune,
                self.ref_axis, self.n_micro,
                _mesh_fingerprint(resolve_mesh(self.mesh, self.mesh_shape)),
                _scalar_or_id(self.excl_zone),
                _scalar_or_id(self.excl_lo), _scalar_or_id(self.excl_hi),
                bool(self.prune) if self.op == "search_topk" else None,
                self.span_cap if self.op == "search_topk" else None,
                bool(self.normalize) if self.op == "search_topk" else None,
                self.engine_impl if self.op == "search_topk" else None,
                ref_id)


@dataclasses.dataclass(frozen=True)
class StreamRequest:
    """One streaming session, as data — ``engine.stream``'s argument
    surface. ``open()`` validates and returns the live session
    (``StreamSession`` or ``ShardedStreamSession``), exactly as the
    keyword front door would. See ``SdtwRequest`` (and the module
    docstring) for the shared field semantics."""
    queries: Any = None
    qlens: Any = None
    metric: str = "abs_diff"
    impl: str = "auto"
    chunk: Optional[int] = None
    mesh: Any = None
    mesh_shape: Any = None
    ref_axis: str = "ref"
    n_micro: Optional[int] = None
    top_k: Optional[int] = None
    excl_zone: Any = None
    excl_mode: str = "end"
    return_spans: bool = False
    return_positions: bool = False
    excl_lo: Any = None
    excl_hi: Any = None
    prune: bool = False
    span_cap: Optional[int] = None
    alert_threshold: Any = None
    on_alert: Any = None
    cache: Any = None
    ref_key: Any = None
    block_q: Optional[int] = None
    block_m: Optional[int] = None
    streams: int = 1

    @classmethod
    def from_kwargs(cls, **kwargs) -> "StreamRequest":
        """Build a request from a kwargs dict, rejecting unknown keys
        loudly."""
        _reject_unknown(cls, kwargs)
        return cls(**kwargs)

    # ------------------------------------------------------------------
    # the shared validator
    # ------------------------------------------------------------------

    def validate(self) -> "StreamRequest":
        """Front-door checks for ``engine.stream`` — the sharded-session
        rejections (pruning/alerts/cache/span_cap are single-process) and
        the session-argument checks, in the pre-request order so error
        messages land unchanged."""
        if self.impl not in STREAM_IMPLS:
            raise ValueError(
                f"impl must be 'auto', 'rowscan', 'pallas' or 'sharded' "
                f"for streaming, got {self.impl!r}")
        mesh = resolve_mesh(self.mesh, self.mesh_shape)
        if self.n_micro is not None and mesh is None \
                and self.impl != "sharded":
            raise ValueError("n_micro= schedules the sharded systolic "
                             "pipeline; pass mesh=/mesh_shape= (or "
                             "impl='sharded') or drop n_micro=")
        if mesh is not None or self.impl == "sharded":
            if self.streams != 1:
                raise ValueError("streams= stacks streams on one device; a "
                                 "sharded session splits one stream over "
                                 "a mesh (drop mesh= or streams=)")
            if self.prune:
                raise ValueError("mesh= streams every chunk; the LB cascade "
                                 "is single-process (drop prune=True)")
            if self.alert_threshold is not None or self.on_alert is not None:
                raise ValueError("alerts are single-process; drop mesh=")
            if self.cache is not None or self.ref_key is not None:
                raise ValueError("the envelope cache is built by the "
                                 "single-process pruning path; "
                                 "cache=/ref_key= have no effect on a "
                                 "sharded session (drop them or drop "
                                 "mesh=)")
            if self.span_cap is not None:
                raise ValueError("span_cap= only bounds the pruned path; a "
                                 "sharded session streams every chunk "
                                 "exactly")
            return self
        return self.validate_session()

    def validate_session(self) -> "StreamRequest":
        """The single-process session checks — ``StreamSession.__init__``
        delegates here, so a directly-constructed session and the
        ``engine.stream`` front door cannot drift."""
        if self.excl_mode not in EXCL_MODES:
            raise ValueError(f"excl_mode must be one of {EXCL_MODES}, got "
                             f"{self.excl_mode!r}")
        if self.top_k is not None and (not isinstance(self.top_k, int)
                                       or self.top_k < 1):
            raise ValueError(f"top_k must be a positive int, got "
                             f"{self.top_k!r}")
        if self.excl_mode == "span" and self.top_k is None \
                and not self.return_spans:
            raise ValueError("excl_mode='span' only affects top-K "
                             "suppression; pass top_k=")
        if (self.excl_lo is None) != (self.excl_hi is None):
            raise ValueError("excl_lo and excl_hi must be given together")
        if self.prune and self.top_k is None:
            raise ValueError("prune=True reports the top-K heap only; "
                             "pass top_k=")
        if self.prune and self.alert_threshold is not None:
            raise ValueError("alerts need every tile's candidate row, "
                             "which pruning skips; use prune=False for a "
                             "threshold monitor")
        if self.impl == "pallas" and self.excl_lo is not None:
            raise ValueError("the pallas kernel does not support "
                             "exclusion zones; use impl='rowscan'")
        if self.chunk is not None and int(self.chunk) < 1:
            raise ValueError(f"chunk must be >= 1, got {int(self.chunk)}")
        if not isinstance(self.streams, int) or self.streams < 1:
            raise ValueError(f"streams must be a positive int, got "
                             f"{self.streams!r}")
        if self.streams > 1 and self.prune:
            raise ValueError("prune=True skips tiles per stream; a "
                             "many-stream session advances every stream "
                             "every tile (drop prune= or streams=)")
        if self.streams > 1 and self.excl_lo is not None:
            raise ValueError("exclusion bands are not supported in a "
                             "many-stream session (drop excl_lo/excl_hi "
                             "or streams=)")
        return self

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def open(self):
        """Validate and open the session — byte-identical to
        ``engine.stream(**kwargs)``, which is a shim over this method."""
        import jax

        from repro.stream import ShardedStreamSession, StreamSession
        self.validate()
        mesh = resolve_mesh(self.mesh, self.mesh_shape)
        if mesh is not None or self.impl == "sharded":
            return ShardedStreamSession(
                self.queries, qlens=self.qlens, metric=self.metric,
                mesh=mesh, axis=self.ref_axis, chunk=self.chunk,
                n_micro=self.n_micro, top_k=self.top_k,
                excl_zone=self.excl_zone, excl_mode=self.excl_mode,
                return_spans=self.return_spans,
                return_positions=self.return_positions,
                excl_lo=self.excl_lo, excl_hi=self.excl_hi)
        impl = self.impl
        if impl == "auto":
            # Only per-query exclusion zones force the rowscan tile loop —
            # top-K heaps, threshold alerts and online pruning all score
            # on the kernel's in-kernel last-row capture.
            impl = ("pallas" if jax.default_backend() == "tpu"
                    and self.excl_lo is None else "rowscan")
        return StreamSession(
            self.queries, qlens=self.qlens, metric=self.metric,
            chunk=self.chunk, impl=impl, top_k=self.top_k,
            excl_zone=self.excl_zone, excl_mode=self.excl_mode,
            return_spans=self.return_spans,
            return_positions=self.return_positions,
            excl_lo=self.excl_lo, excl_hi=self.excl_hi, prune=self.prune,
            span_cap=self.span_cap, alert_threshold=self.alert_threshold,
            on_alert=self.on_alert, cache=self.cache, ref_key=self.ref_key,
            block_q=self.block_q, block_m=self.block_m,
            streams=self.streams)
