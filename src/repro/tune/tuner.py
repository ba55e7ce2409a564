"""The two-stage autotuner and the oracle the engine consults.

Stage 1 (``mode='model'``, the default): the analytical
``KernelCostModel`` ranks candidate configurations for the request's
(backend, metric, dtype, shape bucket: ``bucket_key``); a
shipped/recorded ``TuningTable`` entry overlays the prediction when one
exists.  Stage 2
(``mode='measure'``): the top model candidates are re-ranked by a short
on-device measured search — median of ``reps`` timed runs, compile time
excluded by a warmup call — and the winner is persisted into the
process table (and the LRU), so the measurement runs once per bucket per
process.  ``mode='off'`` never reaches this module: the engine keeps its
legacy hand-tuned constants.

Resolution precedence, everywhere: explicit caller kwargs > measured
table entry > model-source table entry > cost-model prediction.

Measured search never runs on a jitted trace path: the engine resolves
``mode='measure'`` *before* dispatch, and the kernel-level consultation
(``resolve_blocks``) downgrades 'measure' to a table lookup — a
measurement inside ``jax.jit`` tracing would time tracing, not compute.

``python -m repro.tune.tuner --backend interpret --out tables/interpret.json``
re-records a shipped table (see README "Autotuning").
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import numpy as np

from .cache import cached
from .cost import (TunedConfig, bucket_key, get_cost_model, m_bucket,
                   tuned_n_micro, _pow2_bucket)
from .table import TuningTable, default_table

#: Measured-search bounds: candidates whose bucket exceeds this many DP
#: cells fall back to the model for that aspect (recording huge buckets
#: is a deliberate offline act, not a request-path surprise).
MEASURE_CAP_CELLS = 1 << 24
#: Timed repeats per candidate (median taken); one warmup run per
#: candidate excludes compile time.
MEASURE_REPS = 3


def canonical_backend(backend: Optional[str] = None) -> str:
    """Map a jax backend string to a tuning-backend name: 'tpu' keeps its
    own calibration; 'cpu' runs XLA-CPU (pallas in interpret mode) and is
    the 'interpret' family. Any other backend has no constants and is
    refused."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    if backend == "tpu":
        return "tpu"
    if backend in ("cpu", "interpret"):
        return "interpret"
    raise ValueError(f"no tuning backend for jax backend {backend!r}; "
                     "expected 'cpu' or 'tpu'")


@dataclasses.dataclass(frozen=True)
class Resolution:
    """One resolved tuning decision for a bucket: the merged winning
    config, the model's impl ranking (for ``explain=``), and where the
    winner came from."""
    config: TunedConfig
    candidates: tuple      # (('wavefront', us), ('rowscan', us), ...)
    source: str            # 'model' | 'table:model' | 'table:measured'
                           # | 'measured'


def _overlay(base: TunedConfig, entry: TunedConfig) -> TunedConfig:
    """Table entry fields (non-None) win over the model prediction."""
    updates = {k: v for k, v in dataclasses.asdict(entry).items()
               if v is not None and k != "source"}
    return dataclasses.replace(base, **updates)


def resolve(nq: int, n: int, m: int, *, backend: Optional[str] = None,
            metric: str = "abs_diff", dtype: str = "int32",
            mode: str = "model", span: bool = False,
            lastrow: bool = False, rowref: bool = False) -> Resolution:
    """The oracle: LRU -> table -> cost model (-> measured search under
    ``mode='measure'``).  Costs are evaluated at the bucket's shape
    (powers of two; on the TPU a reference up to 4,096 rounded up to
    whole 128-lane vregs, see ``m_bucket``) so every shape in a bucket
    shares one decision; ``span``, ``lastrow`` and ``rowref`` (the
    kernel variant) size the VMEM working set."""
    backend = canonical_backend(backend)
    key = bucket_key(backend, metric, dtype, nq, n, m)

    def compute() -> Resolution:
        model = get_cost_model(backend)
        nb = _pow2_bucket(max(1, nq))
        nn = _pow2_bucket(max(1, n))
        nm = m_bucket(m, backend)
        ranked = tuple(model.rank_impls(nb, nn, nm))
        pal = model.best_pallas(nb, nn, nm, span=span, lastrow=lastrow,
                                rowref=rowref)
        chunk = model.best_chunk(nb, nn, nm)
        cfg = TunedConfig(
            impl=ranked[0][0], block_q=pal.block_q, block_m=pal.block_m,
            scan_scheme=pal.scan_scheme, row_tile=pal.row_tile,
            chunk=chunk, score_us=ranked[0][1], source="model")
        source = "model"
        entry = default_table(backend).get(key)
        if entry is not None:
            cfg = _overlay(cfg, entry)
            source = f"table:{entry.source}"
        if mode == "measure" and (entry is None
                                  or entry.source != "measured"):
            cfg = measured_search(nb, nn, nm, backend=backend,
                                  metric=metric, dtype=dtype, span=span,
                                  lastrow=lastrow, seed_config=cfg)
            default_table(backend).put(key, cfg)
            source = "measured"
        return Resolution(dataclasses.replace(cfg, source=source),
                          ranked, source)

    return cached((key, span, lastrow, rowref, mode), compute)


# ---------------------------------------------------------------------------
# Engine-facing oracle entry points
# ---------------------------------------------------------------------------

def tuned_blocks(b: int, m: int, *, n: int, backend: Optional[str] = None,
                 metric: str = "abs_diff", dtype: str = "int32",
                 mode: str = "model", span: bool = False,
                 lastrow: bool = False, rowref: bool = False) -> tuple:
    """Kernel block knobs for ``resolve_blocks``:
    ``(block_q, block_m, scan_scheme, row_tile)``.  'measure' downgrades
    to the table (see module doc — this is called at trace time)."""
    res = resolve(b, n, m, backend=backend, metric=metric, dtype=dtype,
                  mode="model" if mode == "measure" else mode, span=span,
                  lastrow=lastrow, rowref=rowref)
    c = res.config
    return c.block_q, c.block_m, c.scan_scheme, c.row_tile


def tuned_chunk(nq: int, n: int, m: int, *,
                backend: Optional[str] = None, metric: str = "abs_diff",
                dtype: str = "int32", mode: str = "model") -> int:
    """Reference tile size for the chunked/sharded streaming paths."""
    return resolve(nq, n, m, backend=backend, metric=metric,
                   dtype=dtype, mode=mode).config.chunk


def rank_incore(nq: int, n: int, m: int, *,
                backend: Optional[str] = None, metric: str = "abs_diff",
                dtype: str = "int32", mode: str = "model") -> Resolution:
    """In-core impl choice (rowscan vs wavefront) for ``choose_impl``."""
    return resolve(nq, n, m, backend=backend, metric=metric,
                   dtype=dtype, mode=mode)


def resolve_n_micro(nq: int, n_dp: int, n_mp: int, *, n: int, m: int,
                    backend: Optional[str] = None,
                    metric: str = "abs_diff", dtype: str = "int32",
                    mode: str = "model") -> int:
    """Microbatch count for the sharded systolic schedule: a table entry
    wins (clamped to the schedule's validity envelope), else the
    pipeline-fill default."""
    fill = tuned_n_micro(nq, n_dp, n_mp)
    if mode == "off":
        return fill
    entry = resolve(nq, n, m, backend=backend, metric=metric,
                    dtype=dtype, mode=mode).config.n_micro
    if entry is None:
        return fill
    return max(1, min(int(entry), n_mp, max(1, nq) // max(1, n_dp) or 1))


# ---------------------------------------------------------------------------
# Stage 2: the measured search
# ---------------------------------------------------------------------------

def _bench_data(nq: int, n: int, m: int, dtype: str):
    rng = np.random.default_rng(1234 + nq + n + m)
    if dtype.startswith("int"):
        q = rng.integers(-100, 100, (nq, n)).astype(np.int32)
        r = rng.integers(-100, 100, (m,)).astype(np.int32)
    else:
        q = rng.standard_normal((nq, n)).astype(np.float32)
        r = rng.standard_normal((m,)).astype(np.float32)
    import jax.numpy as jnp
    return jnp.asarray(q), jnp.asarray(r)


def _time_median_us(fn, reps: int = MEASURE_REPS) -> float:
    """Median wall time of ``fn()`` in us; one untimed warmup call first
    so XLA compilation is excluded."""
    import jax
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def measured_search(nq: int, n: int, m: int, *, backend: str,
                    metric: str = "abs_diff", dtype: str = "int32",
                    span: bool = False, lastrow: bool = False,
                    seed_config: Optional[TunedConfig] = None,
                    reps: int = MEASURE_REPS, top: int = 3) -> TunedConfig:
    """Refine the model's top candidates on the actual device.

    Measures (independently, each aspect skipped when the bucket exceeds
    ``MEASURE_CAP_CELLS``): the in-core impl ranking, the top ``top``
    chunk sizes, and the top ``top`` pallas block configs.  Returns the
    merged ``TunedConfig(source='measured')``.  Runs eagerly — never
    call from inside a trace.
    """
    import functools
    model = get_cost_model(backend)
    cells = nq * n * m
    q, r = _bench_data(nq, n, m, dtype)
    cfg = seed_config or TunedConfig()
    best_impl, impl_us = cfg.impl, cfg.score_us

    if cells <= MEASURE_CAP_CELLS:
        from repro.core.sdtw import sdtw_batch
        timed = []
        for impl, _ in model.rank_impls(nq, n, m):
            us = _time_median_us(functools.partial(
                sdtw_batch, q, r, None, metric, impl), reps)
            timed.append((impl, us))
        timed.sort(key=lambda t: t[1])
        best_impl, impl_us = timed[0]

    best_chunk = cfg.chunk
    if m > 4096 and cells <= MEASURE_CAP_CELLS * 4:
        from repro.core.sdtw import sdtw_chunked
        cands = [c for c, _ in model.chunk_candidates(nq, n, m)[:top]]
        timed = [(c, _time_median_us(functools.partial(
            sdtw_chunked, q, r, None, metric, c), reps)) for c in cands]
        timed.sort(key=lambda t: t[1])
        best_chunk = timed[0][0]

    bq, bm, scheme, rt = (cfg.block_q, cfg.block_m, cfg.scan_scheme,
                          cfg.row_tile)
    if cells <= MEASURE_CAP_CELLS:
        from repro.kernels.sdtw import sdtw_pallas
        cands = [c for c, _ in
                 model.pallas_candidates(nq, n, m, span=span,
                                         lastrow=lastrow)[:top]]
        timed = []
        for (cbq, cbm, cscheme, crt) in cands:
            us = _time_median_us(functools.partial(
                sdtw_pallas, q, r, None, metric, block_q=cbq,
                block_m=cbm, scan_scheme=cscheme, row_tile=crt), reps)
            timed.append(((cbq, cbm, cscheme, crt), us))
        timed.sort(key=lambda t: t[1])
        (bq, bm, scheme, rt), _ = timed[0]

    return TunedConfig(impl=best_impl, block_q=bq, block_m=bm,
                       scan_scheme=scheme, row_tile=rt, chunk=best_chunk,
                       n_micro=cfg.n_micro, score_us=impl_us,
                       source="measured")


# ---------------------------------------------------------------------------
# Serve-tier pre-tuning (Router.warmup)
# ---------------------------------------------------------------------------

def pretune_request(request) -> int:
    """Resolve tuning decisions for every pow-2 bucket a request's query
    set will dispatch as, priming the LRU (and, under
    ``request.tune='measure'``, the process table) so the serve request
    path never ranks or measures.  Returns the number of buckets primed.
    """
    mode = getattr(request, "tune", "model")
    if mode == "off":
        return 0
    qs = request.queries
    ref = np.asarray(request.reference)
    m = ref.shape[-1]
    dtype = "int32"
    try:
        dtype = str(np.result_type(
            *( [np.asarray(x) for x in qs] if isinstance(qs, (list, tuple))
               else [np.asarray(qs)] ), ref))
    except TypeError:
        pass
    span = bool(request.return_spans)
    from repro.core.engine import bucketize
    if isinstance(qs, (list, tuple)):
        buckets = bucketize([len(np.asarray(x)) for x in qs])
        shapes = [(len(idxs), blen) for blen, idxs in buckets.items()]
    else:
        arr = np.asarray(qs)
        nq, n = (1, arr.shape[0]) if arr.ndim == 1 else arr.shape
        shapes = [(nq, n)]
    for nq, n in shapes:
        resolve(nq, n, m, metric=request.metric, dtype=dtype, mode=mode,
                span=span)
    return len(shapes)


# ---------------------------------------------------------------------------
# Table recording CLI
# ---------------------------------------------------------------------------

#: Shapes the shipped tables cover: the committed bench shapes plus the
#: smoke lane and the chunked-streaming bench bucket (impl/pallas
#: measurement is capped out there — only the chunk size is measured).
DEFAULT_RECORD_SHAPES = ((2, 16, 256), (4, 32, 1024), (8, 64, 4096),
                         (4, 32, 16384), (8, 16, 4096), (4, 32, 262144))


def record_table(backend: str, shapes=DEFAULT_RECORD_SHAPES, *,
                 reps: int = MEASURE_REPS,
                 provenance: str = "") -> TuningTable:
    """Measure every shape bucket and return a fresh ``TuningTable``."""
    table = TuningTable(backend, provenance=provenance)
    for nq, n, m in shapes:
        nb, nn, nm = _pow2_bucket(nq), _pow2_bucket(n), m_bucket(m, backend)
        key = bucket_key(backend, "abs_diff", "int32", nq, n, m)
        model = get_cost_model(backend)
        ranked = model.rank_impls(nb, nn, nm)
        pal = model.best_pallas(nb, nn, nm)
        seed = TunedConfig(impl=ranked[0][0], block_q=pal.block_q,
                           block_m=pal.block_m,
                           scan_scheme=pal.scan_scheme,
                           row_tile=pal.row_tile,
                           chunk=model.best_chunk(nb, nn, nm),
                           score_us=ranked[0][1])
        cfg = measured_search(nb, nn, nm, backend=backend,
                              seed_config=seed, reps=reps)
        table.put(key, cfg)
        print(f"recorded {key}: {cfg.to_json()}")
    return table


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default=None,
                    help="tuning backend (default: current jax backend)")
    ap.add_argument("--out", required=True, help="table JSON path")
    ap.add_argument("--shapes", default=None,
                    help="semicolon-separated nq,n,m triples "
                         "(default: the committed bench shapes)")
    ap.add_argument("--reps", type=int, default=MEASURE_REPS)
    args = ap.parse_args(argv)
    backend = canonical_backend(args.backend)
    shapes = DEFAULT_RECORD_SHAPES
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in s.split(","))
                       for s in args.shapes.split(";"))
    import platform
    table = record_table(
        backend, shapes, reps=args.reps,
        provenance=f"median-of-{args.reps} measured on "
                   f"{platform.machine()} ({backend})")
    table.save(args.out)
    print(f"wrote {len(table)} entries to {args.out}")


if __name__ == "__main__":
    main()
