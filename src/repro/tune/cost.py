"""The analytical stage of the autotuner: a per-config ``KernelCostModel``.

Every engine execution regime (rowscan / wavefront / chunked / pallas)
is priced in microseconds from the calibrated per-backend constants of
``repro.core.platforms.BackendModel``.  The terms, per regime:

  * ``rowscan``  — N sequential row steps, each a tropical associative
    scan over the full (nq, M) live row: ``N * (row_fixed +
    scan_elem * nq * M)``, with the scan-element cost inflating once the
    live rows outgrow the backend's cache knee.
  * ``wavefront`` — N+M-1 anti-diagonal steps, each touching nq * N
    cells: ``(N+M-1) * (wf_fixed + wf_elem * nq * N)``.  On XLA-CPU the
    per-step cost is ~100x below a rowscan row step, which is why the
    wavefront wins every measured in-core CPU shape (2.5-6.7x).
  * ``chunked``  — rowscan economics per tile plus a per-tile fixed cost
    and one boundary-column crossing per chunk: larger chunks amortize
    the N-row-steps-per-chunk overhead until the nq * chunk live rows
    fall out of cache.
  * ``pallas``   — per grid cell: launch/fill (``tile_fixed``), the
    backend's ``pallas_price``, the HBM streaming term via
    ``launch.roofline.kernel_roofline``, and the padding waste of
    batches that do not fill ``block_q`` (padded tiles are paid in
    full).  The two backends need opposite block shapes, so each has its
    own price.  Interpret mode (``CellPrice``): a per-row cost plus a
    per-cell cost with a scan depth of ``log2(block_q * block_m)``
    passes, each a memory sweep over the block, weighted by the scan
    scheme ('assoc' is the cheap one there) — small tiles win.  The
    compiled TPU kernel (``RowChainPrice``): N DP rows per tile, each
    the larger of its serial chain's latency (picks plus
    ceil(log2(block_m)) lane-shift steps) and the vregs its ``block_q``
    rows carry through those steps — tall blocks win until the vector
    work outweighs the chain.  Configs whose VMEM working set ``vmem_words`` exceeds the
    backend budget are rejected outright — the formula
    ``kernels/sdtw/ops.py`` documents.

The model's absolute numbers are rough; only its *ranking* is consumed
(and CI validates the ranking against the measured rows of
``BENCH_baseline.json`` — see ``repro.tune.validate``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core.platforms import BackendModel, RowChainPrice, backend_model

#: Knobs a tuning decision may set.  ``None`` fields mean "not applicable
#: to the chosen impl" — the oracle only ever fills knobs the caller left
#: unset (explicit kwargs always win).
@dataclasses.dataclass(frozen=True)
class TunedConfig:
    impl: Optional[str] = None
    block_q: Optional[int] = None
    block_m: Optional[int] = None
    scan_scheme: Optional[str] = None
    row_tile: Optional[int] = None
    chunk: Optional[int] = None
    n_micro: Optional[int] = None
    score_us: Optional[float] = None
    source: str = "model"          # 'model' | 'measured' | 'default'

    def to_json(self) -> dict:
        d = {k: v for k, v in dataclasses.asdict(self).items()
             if v is not None}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def _pow2_bucket(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


#: Lanes of a TPU vector register: the compiled kernel's reference tile
#: is a whole number of them, not necessarily a power of two.
TPU_LANES = 128


def m_bucket(m: int, backend: str) -> int:
    """The reference-length bucket a decision is priced and cached at.

    On the TPU a reference up to the largest ``block_m`` rung is rounded
    up to whole 128-lane vregs, so a short reference (a 360-sample
    monitoring tick) is priced and launched as 384 lanes instead of
    512; longer references, and every reference in interpret mode, take
    the next power of two.
    """
    m = max(1, int(m))
    if backend == "tpu":
        lanes = -(-m // TPU_LANES) * TPU_LANES
        if lanes <= max(KernelCostModel.BLOCK_M_CANDIDATES):
            return lanes
    return _pow2_bucket(m)


def bucket_key(backend: str, metric: str, dtype: str,
               nq: int, n: int, m: int) -> str:
    """The (backend, metric, dtype, shape bucket) table key.

    Batch and query length are bucketed to the next power of two — the
    same bucketing the engine's ragged dispatch uses — and the reference
    length by ``m_bucket``, so the table stays O(log shape) instead of
    one entry per distinct size.
    """
    return (f"{backend}/{metric}/{dtype}/b{_pow2_bucket(max(1, nq))}"
            f"/n{_pow2_bucket(max(1, n))}/m{m_bucket(m, backend)}")


class KernelCostModel:
    """Prices engine configurations for one backend (see module doc)."""

    #: chunk sizes the chunked oracle ranks.
    CHUNK_CANDIDATES = (4096, 8192, 16384, 32768, 65536, 131072)
    #: reference-tile sizes the pallas oracle ranks (clamped to shape).
    BLOCK_M_CANDIDATES = (256, 512, 1024, 2048, 4096)
    #: query-block sizes the compiled (TPU) kernel ranks, capped at the
    #: batch rounded up to the sublane multiple 8.
    TPU_BLOCK_Q_CANDIDATES = (8, 16, 32, 64, 128, 256)
    #: the most vregs one live (block_q, block_m) array of a TPU candidate
    #: may span: the block sweep measured up to 64, where the cells per
    #: second had stopped growing, and compile time grows with it.
    TPU_MAX_BLOCK_VREGS = 64

    def __init__(self, backend: "str | BackendModel" = "interpret"):
        self.backend = (backend if isinstance(backend, BackendModel)
                        else backend_model(backend))

    # -- the documented VMEM working-set formula ------------------------

    @staticmethod
    def vmem_words(block_q: int, block_m: int, n: int,
                   span: bool = False, lastrow: bool = False,
                   rowref: bool = False) -> int:
        """Accumulator words live per pallas grid cell — identical to the
        formula in the ``sdtw_pallas`` docstring (boundary column in
        persistent scratch + ~3 (plain) / ~6 (span) live row vectors,
        span mode adding the int32 start lanes), plus the double-buffered
        ``return_lastrow`` output block and its start lane, and the
        double-buffered per-row reference tile."""
        words = (block_q * (6 * block_m + 5 * n) if span
                 else block_q * (3 * block_m + 3 * n))
        if lastrow:
            words += 2 * block_q * block_m * (2 if span else 1)
        if rowref:
            words += 2 * block_q * block_m
        return words

    # -- per-regime cost (microseconds) ---------------------------------

    def _scan_elem(self, live_elems: int) -> float:
        """Row-scan per-element cost, inflated past the cache knee."""
        be = self.backend
        over = max(0.0, math.log2(max(1, live_elems) / be.cache_elems))
        return be.scan_elem_us * (1.0 + 0.25 * over)

    def rowscan_us(self, nq: int, n: int, m: int) -> float:
        be = self.backend
        return be.call_fixed_us + n * (
            be.row_step_fixed_us + self._scan_elem(nq * m) * nq * m)

    def wavefront_us(self, nq: int, n: int, m: int) -> float:
        be = self.backend
        steps = n + m - 1
        return be.call_fixed_us + steps * (
            be.wf_step_fixed_us + be.wf_elem_us * nq * n)

    def chunked_us(self, nq: int, n: int, m: int, chunk: int) -> float:
        be = self.backend
        n_chunks = -(-m // chunk)
        per_row = be.row_step_fixed_us \
            + self._scan_elem(nq * chunk) * nq * chunk
        return (be.call_fixed_us + n_chunks * be.chunk_fixed_us
                + n_chunks * n * per_row)

    def pallas_us(self, nq: int, n: int, m: int, block_q: int,
                  block_m: int, scan_scheme: str, row_tile: int,
                  span: bool = False, lastrow: bool = False,
                  rowref: bool = False) -> float:
        """One pallas launch over the full grid; ``inf`` when the config
        busts the VMEM budget (never a candidate)."""
        be = self.backend
        if self.vmem_words(block_q, block_m, n, span, lastrow, rowref) \
                > be.vmem_budget_words:
            return float("inf")
        q_tiles = -(-nq // block_q)
        m_tiles = -(-max(m, block_m) // block_m)
        tiles = q_tiles * m_tiles
        # HBM streaming: the reference is re-read once per query tile (a
        # per-row reference once per query), queries once per reference
        # tile, boundary column stays in VMEM scratch (free); 4-byte
        # accumulator words.
        ref_rows = q_tiles * block_q if rowref else q_tiles
        hbm_bytes = 4 * (ref_rows * m + m_tiles * block_q * n)
        from repro.launch.roofline import kernel_roofline
        hbm_us = kernel_roofline(
            0, hbm_bytes, cells_per_s=1.0,
            hbm_bw=be.hbm_bw_bytes_per_s)[0] * 1e6
        price = be.pallas_price
        if isinstance(price, RowChainPrice):
            # Compiled kernel: a tile pays its row chain N times; padding
            # waste is the padded tiles themselves.
            return (be.call_fixed_us + tiles * be.tile_fixed_us
                    + tiles * n * self.row_chain_us(block_q, block_m,
                                                    span)
                    + hbm_us)
        # Padding waste: cells are computed on the padded grid.
        cells = (q_tiles * block_q) * n * (m_tiles * block_m)
        passes = math.log2(max(2, block_q * block_m))
        elem = price.elem_us + price.pass_us * passes \
            * price.scheme_cost_mult(scan_scheme)
        rt_mult = 1.0 + 0.02 * max(0, 8 // max(1, row_tile) - 1)
        return (be.call_fixed_us + tiles * be.tile_fixed_us
                + tiles * n * price.row_fixed_us * rt_mult
                + cells * elem + hbm_us)

    @staticmethod
    def block_vregs(block_q: int, block_m: int) -> int:
        """(8, 128) vregs one (block_q, block_m) int32 array spans."""
        return -(-block_q // 8) * -(-block_m // 128)

    def row_chain_us(self, block_q: int, block_m: int,
                     span: bool = False) -> float:
        """Compiled-kernel time of one (tile, DP row): the larger of the
        row's dependent-chain latency and its vector work (see
        ``repro.core.platforms.RowChainPrice``). The scan runs
        ceil(log2(block_m)) shift steps: 9 for a 384-lane block, as for
        512."""
        c = self.backend.pallas_price
        steps = (int(block_m) - 1).bit_length()
        latency = c.lat_fixed_us + c.lat_step_us * steps
        work = c.vreg_step_us * (self.block_vregs(block_q, block_m) * steps
                                 + c.pick_vreg_steps * -(-block_q // 8))
        if span:
            latency *= c.span_lat_mult
            work *= c.span_work_mult
        return max(latency, work)

    # -- candidate enumeration / ranking --------------------------------

    def rank_impls(self, nq: int, n: int, m: int,
                   impls=("wavefront", "rowscan")) -> list:
        """Ranked ``[(impl, predicted_us), ...]``, cheapest first."""
        scored = []
        for impl in impls:
            if impl == "rowscan":
                us = self.rowscan_us(nq, n, m)
            elif impl == "wavefront":
                us = self.wavefront_us(nq, n, m)
            elif impl == "chunked":
                us = self.chunked_us(nq, n, m, self.best_chunk(nq, n, m))
            elif impl == "pallas":
                us = self.pallas_candidates(nq, n, m)[0][1]
            else:
                continue
            scored.append((impl, us))
        scored.sort(key=lambda t: t[1])
        return scored

    def chunk_candidates(self, nq: int, n: int, m: int) -> list:
        """Ranked ``[(chunk, predicted_us), ...]`` for the chunked path."""
        cands = sorted({min(c, _pow2_bucket(m))
                        for c in self.CHUNK_CANDIDATES})
        scored = [(c, self.chunked_us(nq, n, m, c)) for c in cands]
        scored.sort(key=lambda t: t[1])
        return scored

    def best_chunk(self, nq: int, n: int, m: int) -> int:
        return self.chunk_candidates(nq, n, m)[0][0]

    def pallas_candidates(self, nq: int, n: int, m: int,
                          span: bool = False, lastrow: bool = False,
                          rowref: bool = False) -> list:
        """Ranked ``[((block_q, block_m, scheme, row_tile), us), ...]``.

        The candidate set stays deliberately small (it seeds the measured
        stage): block_m the pow-2 ladder clamped to the reference's
        ``m_bucket`` (on the TPU {256, 384} for m = 360), the
        scan schemes the backend lowers. Interpret mode: block_q from 1
        up to the batch, no row unrolling. The compiled kernel: block_q
        the sublane multiples of ``TPU_BLOCK_Q_CANDIDATES`` up to the
        batch rounded up to 8, 'shift' only, and ``tpu_row_tile`` rows
        unrolled. Configs that bust the VMEM budget are dropped.
        """
        compiled = isinstance(self.backend.pallas_price, RowChainPrice)
        if compiled:
            cap = -(-max(1, nq) // 8) * 8
            bq_cands = [bq for bq in self.TPU_BLOCK_Q_CANDIDATES
                        if bq <= cap]
            schemes = ("shift",)    # Mosaic cannot lower 'assoc'
        else:
            bq_cands = sorted({bq for bq in (1, 2, 4, 8, 16, 32)
                               if bq <= max(1, nq)} | {min(32, max(1, nq))})
            schemes = ("assoc", "shift")
        bm_cands = sorted({min(bm, m_bucket(m, self.backend.name))
                           for bm in self.BLOCK_M_CANDIDATES})
        scored = []
        for bq in bq_cands:
            for bm in bm_cands:
                if compiled and (self.block_vregs(bq, bm)
                                 > self.TPU_MAX_BLOCK_VREGS):
                    continue
                rt = self.tpu_row_tile(bq, bm) if compiled else 1
                for scheme in schemes:
                    us = self.pallas_us(nq, n, m, bq, bm, scheme, rt,
                                        span=span, lastrow=lastrow,
                                        rowref=rowref)
                    if math.isfinite(us):
                        scored.append(((bq, bm, scheme, rt), us))
        scored.sort(key=lambda t: t[1])
        if not scored:
            raise ValueError(
                f"no pallas config fits the VMEM budget for nq={nq} "
                f"n={n} m={m} (span={span}, lastrow={lastrow}, "
                f"rowref={rowref})")
        return scored

    @classmethod
    def tpu_row_tile(cls, block_q: int, block_m: int) -> int:
        """DP rows the compiled kernel unrolls: 8 while a row's arrays
        span at most 2 vregs (unrolling overlaps the latency-bound rows),
        else 2 (the rows are throughput-bound, and the unrolled body and
        its compile time grow with the rows unrolled)."""
        return 8 if cls.block_vregs(block_q, block_m) <= 2 else 2

    def best_pallas(self, nq: int, n: int, m: int, span: bool = False,
                    lastrow: bool = False,
                    rowref: bool = False) -> TunedConfig:
        (bq, bm, scheme, rt), us = self.pallas_candidates(
            nq, n, m, span=span, lastrow=lastrow, rowref=rowref)[0]
        return TunedConfig(impl="pallas", block_q=bq, block_m=bm,
                           scan_scheme=scheme, row_tile=rt, score_us=us)


def tuned_n_micro(nq: int, n_dp: int, n_mp: int) -> int:
    """Pipeline-fill microbatch count: as many microbatches per dp row as
    the systolic depth can overlap (``n_mp``) without any slot being pure
    padding — the fill/drain bubble is ``(n_mp - 1) / (n_micro + n_mp - 1)``
    of the schedule, so more (real) microbatches amortize it.  Mirrors
    ``distributed.sdtw_sharded.make_schedule``'s default so the engine
    can report (and the table can override) the choice explicitly."""
    return max(1, min(n_mp, -(-max(1, nq) // n_dp)))


_MODELS: dict = {}


def get_cost_model(backend: str) -> KernelCostModel:
    """Process-cached ``KernelCostModel`` per backend name."""
    if backend not in _MODELS:
        _MODELS[backend] = KernelCostModel(backend)
    return _MODELS[backend]
