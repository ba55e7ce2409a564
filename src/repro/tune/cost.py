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
  * ``pallas``   — per grid cell: launch/fill (``tile_fixed``), a per-row
    cost, and a per-cell cost with a scan-depth term — ``pass_us *
    log2(block_q * block_m)`` scan passes, weighted by the backend's
    scheme multiplier ('shift' Hillis-Steele is the cheap scheme on TPU,
    the work-efficient 'assoc' in interpret mode) — plus the HBM
    streaming term via ``launch.roofline.kernel_roofline`` and a padding
    -waste factor for batches that do not fill ``block_q``.  Configs
    whose VMEM working set ``block_q * (3*block_m + 3*N)`` words (span
    mode ``block_q * (6*block_m + 5*N)``) exceed the backend budget are
    rejected outright — the same formula ``kernels/sdtw/ops.py``
    documents.

The model's absolute numbers are rough; only its *ranking* is consumed
(and CI validates the ranking against the measured rows of
``BENCH_baseline.json`` — see ``repro.tune.validate``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.core.platforms import BackendModel, backend_model

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


def bucket_key(backend: str, metric: str, dtype: str,
               nq: int, n: int, m: int) -> str:
    """The (backend, metric, dtype, pow-2 shape bucket) table key.

    Shapes are bucketed to the next power of two — the same bucketing
    the engine's ragged dispatch uses — so the table stays O(log shape)
    instead of one entry per distinct size.
    """
    return (f"{backend}/{metric}/{dtype}/b{_pow2_bucket(max(1, nq))}"
            f"/n{_pow2_bucket(max(1, n))}/m{_pow2_bucket(max(1, m))}")


class KernelCostModel:
    """Prices engine configurations for one backend (see module doc)."""

    #: chunk sizes the chunked oracle ranks.
    CHUNK_CANDIDATES = (4096, 8192, 16384, 32768, 65536, 131072)
    #: reference-tile sizes the pallas oracle ranks (clamped to shape).
    BLOCK_M_CANDIDATES = (256, 512, 1024, 2048, 4096)

    def __init__(self, backend: "str | BackendModel" = "interpret"):
        self.backend = (backend if isinstance(backend, BackendModel)
                        else backend_model(backend))

    # -- the documented VMEM working-set formula ------------------------

    @staticmethod
    def vmem_words(block_q: int, block_m: int, n: int,
                   span: bool = False) -> int:
        """Accumulator words live per pallas grid cell — identical to the
        formula in the ``sdtw_pallas`` docstring (boundary column in
        persistent scratch + ~3 (plain) / ~6 (span) live row vectors,
        span mode adding the int32 start lanes)."""
        if span:
            return block_q * (6 * block_m + 5 * n)
        return block_q * (3 * block_m + 3 * n)

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
                  span: bool = False) -> float:
        """One pallas launch over the full grid; ``inf`` when the config
        busts the VMEM budget (never a candidate)."""
        be = self.backend
        if self.vmem_words(block_q, block_m, n, span) \
                > be.vmem_budget_words:
            return float("inf")
        q_tiles = -(-nq // block_q)
        m_tiles = -(-max(m, block_m) // block_m)
        tiles = q_tiles * m_tiles
        # Padding waste: cells are computed on the padded grid.
        cells = (q_tiles * block_q) * n * (m_tiles * block_m)
        passes = math.log2(max(2, block_q * block_m))
        elem = be.pallas_elem_us + be.pallas_pass_us * passes \
            * be.scheme_cost_mult(scan_scheme)
        # HBM streaming: the reference is re-read once per query tile,
        # queries once per reference tile, boundary column stays in VMEM
        # scratch (free); 4-byte accumulator words.
        hbm_bytes = 4 * (q_tiles * m + m_tiles * block_q * n)
        from repro.launch.roofline import kernel_roofline
        hbm_us = kernel_roofline(
            0, hbm_bytes, cells_per_s=1.0,
            hbm_bw=be.hbm_bw_bytes_per_s)[0] * 1e6
        rt_mult = 1.0 + 0.02 * max(0, 8 // max(1, row_tile) - 1)
        return (be.call_fixed_us + tiles * be.tile_fixed_us
                + tiles * n * be.pallas_row_fixed_us * rt_mult
                + cells * elem + hbm_us)

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
                          span: bool = False) -> list:
        """Ranked ``[((block_q, block_m, scheme, row_tile), us), ...]``.

        The candidate set stays deliberately small (it seeds the measured
        stage): block_q from 1 up to the batch (interpret) or the sublane
        multiple 8 (TPU), block_m the pow-2 ladder clamped to the
        reference, the scan schemes the backend lowers (TPU: 'shift'
        only), the backend's natural row_tile.
        """
        interpret = self.backend.name != "tpu"
        if interpret:
            bq_cands = sorted({bq for bq in (1, 2, 4, 8, 16, 32)
                               if bq <= max(1, nq)} | {min(32, max(1, nq))})
            rt = 1
            schemes = ("assoc", "shift")
        else:
            bq_cands = [8, 16]
            rt = 8
            schemes = ("shift",)    # Mosaic cannot lower 'assoc'
        bm_cands = sorted({min(bm, _pow2_bucket(m))
                           for bm in self.BLOCK_M_CANDIDATES})
        scored = []
        for bq in bq_cands:
            for bm in bm_cands:
                for scheme in schemes:
                    us = self.pallas_us(nq, n, m, bq, bm, scheme, rt,
                                        span=span)
                    if math.isfinite(us):
                        scored.append(((bq, bm, scheme, rt), us))
        scored.sort(key=lambda t: t[1])
        if not scored:
            raise ValueError(
                f"no pallas config fits the VMEM budget for nq={nq} "
                f"n={n} m={m} (span={span})")
        return scored

    def best_pallas(self, nq: int, n: int, m: int,
                    span: bool = False) -> TunedConfig:
        (bq, bm, scheme, rt), us = self.pallas_candidates(
            nq, n, m, span=span)[0]
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
