"""Baseline platform models (CPU / GPU / FPGA / PNM) for Table VI.

The paper measures real hardware (cpui7/cpuxeon via RAPL, GPU via nvidia-smi,
UPMEM/FPGA/ARM via vendor tools or ZSim+McPAT). That hardware is unavailable
here, so each baseline is an analytic (throughput, power) model anchored to:

  1. the paper's own §II-D characterization (measured sustained GINTOPS,
     arithmetic intensity, utilization), and
  2. public hardware specs (TDP, bandwidth, core counts).

Derivation trail (full napkin math in EXPERIMENTS.md §Paper-validation):

  * sDTW inner loop ≈ 8 integer ops/cell (sub, abs, 2 cmp, 2 sel, add, +addr).
  * gpu:   §II-D measures ~1% of 15.7 TINTOPS peak → ~157 GINTOPS sustained
           → 19.7 GCells/s; V100 TDP 300W (+HBM) → ~17 nJ/cell.
  * upmem: compute-bound at DPU throughput (paper: 146 GINTOPS peak) →
           ~19.4 GCells/s; power set so UPMEM energy = 0.63× GPU — the
           paper's measured "37% reduction" (§II-D) — → ~10.8 nJ/cell.
  * cpuxeon: memory-bound; 2×Xeon 6154 (~230 GB/s, AI 0.55 INTOP/B measured
           on the Phi → ~127 GINTOPS ceiling, 41% util class) → ~16.7 GCells/s;
           2-socket server wall power ~700W.
  * cpui7 / cpuarm / fpga: scaled the same way from §IV-C's reported ratios
           against MATSA-Portable/Embedded and public TDPs.

These constants make the baselines *independent* of the MATSA model (they are
cells/s + watts), so Table VI ratios computed by ``benchmarks/table6`` are a
genuine cross-check of the MATSA PUM model, not an identity.
"""
from __future__ import annotations

import dataclasses

from .pum_model import Workload


@dataclasses.dataclass(frozen=True)
class PlatformModel:
    name: str
    cells_per_s: float        # sustained sDTW DP-cell throughput
    watts: float              # average package power during the kernel
    peak_gintops: float       # platform peak (for roofline reporting)
    ai_intop_per_byte: float  # measured arithmetic intensity (paper §II-D)
    note: str = ""

    def exec_time_s(self, w: Workload) -> float:
        return w.num_queries * w.query_size * w.ref_size / self.cells_per_s

    def energy_j(self, w: Workload) -> float:
        return self.exec_time_s(w) * self.watts

    def energy_per_cell_j(self) -> float:
        return self.watts / self.cells_per_s

    def utilization(self, ops_per_cell: float = 8.0) -> float:
        return self.cells_per_s * ops_per_cell / (self.peak_gintops * 1e9)


@dataclasses.dataclass(frozen=True)
class CellPrice:
    """The interpret-mode kernel's price, per grid tile: a per-row cost
    and a per-cell cost with a scan-depth term of log2(block_q * block_m)
    passes, each pass a memory sweep over the whole block, weighted by
    the scan scheme."""
    row_fixed_us: float      # per DP row per grid tile
    elem_us: float           # per DP cell, scheme-independent base
    pass_us: float           # per DP cell per scan *pass* (depth term)
    scheme_mult: tuple       # (('shift', x), ('assoc', y)) pass-cost
                             # multipliers

    def scheme_cost_mult(self, scheme: str) -> float:
        return dict(self.scheme_mult)[scheme]


@dataclasses.dataclass(frozen=True)
class RowChainPrice:
    """The compiled kernel's price of one (grid tile, DP row), in us.

    A DP row is a chain of dependent steps (``kernels/sdtw/sdtw.py``,
    ``one_row``): one-hot lane picks of the query value and the boundary
    entry, ceil(log2(block_m)) Hillis-Steele lane-shift steps (9 for a
    384-lane block, as for 512), and the pick of the exit lane; the next
    row waits for it. The scan runs along lanes only, and the ``block_q``
    rows ride the same vector instructions, so a row costs the larger of
    the chain's latency and the vector work it carries:

        max(lat_fixed_us + lat_step_us * ceil(log2(block_m)),
            vreg_step_us * (ceil(block_q/8) * ceil(block_m/128)
                            * ceil(log2(block_m))
                            + pick_vreg_steps * ceil(block_q/8)))

    the latency times ``span_lat_mult`` and the work times
    ``span_work_mult`` in span mode (the start lanes ride every step).
    """
    lat_fixed_us: float      # the picks and the exit lane, per row
    lat_step_us: float       # one lane-shift step on the dependent chain
    vreg_step_us: float      # one (8, 128) vreg through one scan step
    pick_vreg_steps: float   # the picks' work per 8 queries, in vreg-steps
    span_lat_mult: float     # span variant over plain: latency
    span_work_mult: float    # span variant over plain: vector work


@dataclasses.dataclass(frozen=True)
class BackendModel:
    """Calibrated per-term execution-cost constants for one *execution
    backend of this repo* (as opposed to ``PlatformModel``, which models
    the paper's baseline hardware as whole-kernel cells/s).

    ``repro.tune.cost.KernelCostModel`` prices every engine regime
    (rowscan / wavefront / chunked / pallas) per configuration from these
    constants; the units are microseconds per the named event. The
    ``interpret`` constants were fitted to in-container XLA-CPU
    measurements of the committed bench shapes (see
    ``repro/tune/tables/interpret.json`` provenance). The ``tpu``
    constants (``TPU_BACKENDS``, keyed by device kind) price the compiled
    kernel by its row chain, fitted to a block sweep on the chip; their
    other terms are anchored to the chip's published roofline.
    """
    name: str                    # 'interpret' (XLA CPU) | 'tpu'
    call_fixed_us: float         # per-dispatch overhead of one jitted call
    row_step_fixed_us: float     # per sequential DP row step (rowscan)
    scan_elem_us: float          # per accumulator element per row scan
    wf_step_fixed_us: float      # per anti-diagonal step (wavefront)
    wf_elem_us: float            # per (query-row) element per wavefront step
    chunk_fixed_us: float        # per reference tile (chunked streaming)
    cache_elems: int             # live-row working-set knee (elements);
                                 # beyond it scan_elem_us inflates
    tile_fixed_us: float         # per pallas grid cell (launch/fill)
    pallas_price: "CellPrice | RowChainPrice"
                                 # the kernel's per-tile price: the two
                                 # backends need opposite block shapes
    hbm_bw_bytes_per_s: float    # streaming bandwidth for the HBM term
    vmem_budget_words: int       # pallas per-config working-set cap


#: XLA-CPU (pallas interpret mode) — fitted to this container's measured
#: bench shapes: rowscan ~0.027us/elem/row + ~60us/row-step; wavefront
#: ~0.004us/elem/step + ~0.4us/step (why the wavefront wins every CPU
#: in-core shape, 2.5-6.7x measured); interpret-mode pallas pays a
#: per-scan-pass cost that grows with log2(block_m * block_q), so small
#: tiles win despite more grid cells.
INTERPRET_BACKEND = BackendModel(
    name="interpret", call_fixed_us=500.0, row_step_fixed_us=60.0,
    scan_elem_us=0.027, wf_step_fixed_us=0.4, wf_elem_us=0.004,
    chunk_fixed_us=200.0, cache_elems=1 << 17, tile_fixed_us=150.0,
    pallas_price=CellPrice(row_fixed_us=30.0, elem_us=0.01, pass_us=0.013,
                           scheme_mult=(("assoc", 1.0), ("shift", 1.6))),
    hbm_bw_bytes_per_s=20e9, vmem_budget_words=1 << 21)

#: TPU v5e (819 GB/s HBM, ~16 MB VMEM/core): the vector unit runs the
#: Hillis-Steele 'shift' scan (the only scheme Mosaic lowers), and the
#: binding constraints on the block are the VMEM working set
#: (``KernelCostModel.vmem_words``) and ``TPU_MAX_BLOCK_VREGS``. The
#: row-chain price and ``tile_fixed_us`` are a least-squares fit (log
#: error) to ``bench/block_sweep.py`` on one "TPU v5 lite" chip: 133
#: configurations at the model's row_tile (block_q 8-256, block_m
#: 128-4096, N 64, 120, 512 and 1536, plain, spans and last-row capture),
#: rms error 9 %; within each (variant, N) the fitted pick runs within
#: 4 % of the fastest block measured (PERF.md §6). The rowscan, wavefront
#: and chunked terms are roofline-anchored guesses: on the TPU those
#: regimes run only when forced.
TPU_V5E_BACKEND = BackendModel(
    name="tpu", call_fixed_us=30.0, row_step_fixed_us=2.0,
    scan_elem_us=0.0004, wf_step_fixed_us=1.0, wf_elem_us=0.001,
    chunk_fixed_us=40.0, cache_elems=1 << 21, tile_fixed_us=2.99,
    pallas_price=RowChainPrice(lat_fixed_us=0.523, lat_step_us=0.0345,
                               vreg_step_us=0.00352, pick_vreg_steps=7.67,
                               span_lat_mult=1.14, span_work_mult=1.70),
    hbm_bw_bytes_per_s=819e9, vmem_budget_words=1 << 21)

#: TPU cost constants keyed by the ``device_kind`` the chip reports
#: (``jax.devices()[0].device_kind``; a v5e reports "TPU v5 lite"). A chip
#: missing here is an error, never priced as some other chip.
TPU_BACKENDS = {"TPU v5 lite": TPU_V5E_BACKEND}


def tpu_device_kind() -> str:
    """The ``device_kind`` of the attached TPU; an error off-TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU attached (default device is "
                           f"{dev.platform!r}); TPU cost constants need one")
    return dev.device_kind


def backend_model(name: str) -> BackendModel:
    """The cost-constant set for a tuning backend: 'interpret' (the CPU,
    where the kernel runs in interpret mode) or 'tpu' (looked up by the
    attached chip's ``device_kind``). Anything else is an error."""
    if name == "interpret":
        return INTERPRET_BACKEND
    if name != "tpu":
        raise ValueError(f"no cost constants for backend {name!r}; "
                         "expected 'interpret' or 'tpu'")
    kind = tpu_device_kind()
    if kind not in TPU_BACKENDS:
        raise ValueError(f"no TPU cost constants for device_kind {kind!r}; "
                         f"known kinds: {sorted(TPU_BACKENDS)}")
    return TPU_BACKENDS[kind]


CPU_ARM = PlatformModel(
    "cpuarm", cells_per_s=0.133e9, watts=24.8, peak_gintops=40.0,
    ai_intop_per_byte=0.55,
    note="4-core ARM @2.5GHz, LPDDR4; ZSim+Ramulator+McPAT in the paper")
CPU_I7 = PlatformModel(
    "cpui7", cells_per_s=3.09e9, watts=134.0, peak_gintops=614.0,
    ai_intop_per_byte=0.55,
    note="6C/12T i7 @3.2GHz AVX2, DDR4; RAPL-measured in the paper")
CPU_XEON = PlatformModel(
    "cpuxeon", cells_per_s=16.7e9, watts=769.0, peak_gintops=6900.0,
    ai_intop_per_byte=0.55,
    note="2×18C Xeon Gold 6154 AVX-512, 768GB DDR4; memory-bound (§II-D)")
GPU = PlatformModel(
    "gpu", cells_per_s=19.9e9, watts=342.0, peak_gintops=15700.0,
    ai_intop_per_byte=0.55,
    note="V100 32GB HBM; §II-D measures ~1% of peak INT throughput")
FPGA = PlatformModel(
    "fpga", cells_per_s=0.49e9, watts=49.0, peak_gintops=600.0,
    ai_intop_per_byte=0.55,
    note="Alveo U50, 8 HLS compute units, <7% of peak (§II-D)")
UPMEM = PlatformModel(
    "upmem", cells_per_s=19.4e9, watts=210.0, peak_gintops=146.0,
    ai_intop_per_byte=3.0,
    note="2560 DPUs @425MHz; compute-bound (§II-D); energy = 0.63× GPU")

PLATFORMS = {p.name: p for p in
             (CPU_ARM, CPU_I7, CPU_XEON, GPU, FPGA, UPMEM)}

# Paper Table VI — the claims we validate against.
PAPER_TABLE6 = {
    ("matsa-embedded", "cpuarm"): (30.20, 45.67),
    ("matsa-portable", "cpui7"): (10.41, 10.65),
    ("matsa-portable", "fpga"): (65.01, 24.58),
    ("matsa-hpc", "cpuxeon"): (7.35, 11.29),
    ("matsa-hpc", "upmem"): (6.31, 2.65),
    ("matsa-hpc", "gpu"): (6.15, 4.21),
}
