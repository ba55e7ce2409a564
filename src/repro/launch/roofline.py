"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Accounting conventions (important — recorded with every artifact):
  * ``compiled.cost_analysis()`` runs on the *partitioned* (per-device)
    module → flops / bytes are per-chip. The compute term is therefore
    flops / peak_flops (the "chips ×" in the global formula cancels).
  * collective bytes are summed from result shapes of every
    all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute in ``compiled.as_text()`` (per-device program →
    per-chip bytes on the wire); term = bytes / link_bw. ``-done`` halves of
    async pairs are skipped to avoid double counting.
  * MODEL_FLOPS is the analytic useful-work estimate (6·N·D dense training /
    2·N_active·D forward + exact-causal attention + SSD terms); the ratio
    MODEL_FLOPS / (chips · HLO_FLOPs) exposes remat/padding/masked-half
    waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

#: Published per-chip peaks keyed by the ``device_kind`` JAX reports.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s interchip interconnect; ``ici_bw`` is the
#: per-link share the dry-run accounting assumes).
PEAKS = {
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
#: The dry-run's production meshes are v5e pods.
V5E = PEAKS["TPU v5 lite"]


def chip_peaks(device_kind: str) -> dict:
    """Peaks of the chip JAX reports as ``device_kind``; an unknown kind
    is an error, never another chip's numbers."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^=]*?\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip bytes moved by each collective type (result-shape proxy)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = {k: 0 for k in out}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op, phase = m.groups()
        if phase == "-done":
            continue
        out[op] += _shape_bytes(shape_txt)
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_per_chip: float
    hlo_bytes_per_chip: float
    coll_bytes_per_chip: float
    n_chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops_per_chip * self.n_chips
        return self.model_flops / total if total else float("nan")

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound: useful flops / (chips · peak · bound_time)."""
        denom = self.n_chips * V5E["peak_flops"] * self.bound_time_s
        return self.model_flops / denom if denom else float("nan")

    def to_dict(self):
        return {**dataclasses.asdict(self),
                "dominant": self.dominant,
                "bound_time_s": self.bound_time_s,
                "useful_flops_ratio": self.useful_flops_ratio,
                "roofline_fraction": self.roofline_fraction}


def roofline(flops_per_chip: float, bytes_per_chip: float,
             coll_bytes_per_chip: float, model_flops: float,
             n_chips: int, hw=None) -> RooflineTerms:
    hw = hw or V5E
    return RooflineTerms(
        compute_s=flops_per_chip / hw["peak_flops"],
        memory_s=bytes_per_chip / hw["hbm_bw"],
        collective_s=coll_bytes_per_chip / hw["ici_bw"],
        model_flops=model_flops,
        hlo_flops_per_chip=flops_per_chip,
        hlo_bytes_per_chip=bytes_per_chip,
        coll_bytes_per_chip=coll_bytes_per_chip,
        n_chips=n_chips)


def kernel_roofline(cells: float, hbm_bytes: float, *,
                    cells_per_s: float, hbm_bw: Optional[float] = None):
    """Two-term roofline bound for one sDTW kernel configuration.

    Unlike :func:`roofline` (which extracts terms from compiled HLO), this
    prices an *analytic* configuration before anything is compiled — the
    autotuner (``repro.tune.cost``) calls it per candidate: ``cells`` DP
    cells at the backend's sustained ``cells_per_s`` versus ``hbm_bytes``
    of streaming traffic at ``hbm_bw``. Returns
    ``(bound_time_s, dominant)`` where dominant is 'compute' or 'memory'.
    """
    hbm_bw = V5E["hbm_bw"] if hbm_bw is None else hbm_bw
    compute_s = cells / cells_per_s if cells_per_s else 0.0
    memory_s = hbm_bytes / hbm_bw if hbm_bw else 0.0
    return (max(compute_s, memory_s),
            "compute" if compute_s >= memory_s else "memory")


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS per cell
# ---------------------------------------------------------------------------

def model_flops(cfg, shape) -> float:
    """Useful-work FLOPs for one step of this cell (whole mesh)."""
    b, s = shape.global_batch, shape.seq_len
    v, d = cfg.vocab, cfg.d_model
    n_active = cfg.active_param_count()
    # Embedding lookups are gather (0 flops); logits matmul is real.
    n_mm = n_active - (0 if cfg.tie_embeddings else v * d)

    n_attn = 0
    if cfg.n_heads:
        n_attn = (cfg.n_layers if cfg.family != "hybrid"
                  else cfg.n_layers // cfg.attn_every)
    hd = cfg.resolved_head_dim
    attn_fwd_per_tok = 2 * (s / 2) * cfg.n_heads * hd * 2 * n_attn \
        if shape.kind != "decode" else 0   # exact causal: S/2 avg context

    ssd_fwd_per_tok = 0.0
    if cfg.has_ssm:
        L, n_state, di = cfg.ssm_chunk, cfg.ssm_state, cfg.d_inner
        # G=CBᵀ, scores·X, state-in, y_inter per layer
        ssd_fwd_per_tok = (2 * L * n_state + 2 * L * di
                           + 4 * n_state * di) * cfg.n_layers

    if shape.kind == "train":
        tokens = b * s
        return (6 * n_mm + 3 * (attn_fwd_per_tok + ssd_fwd_per_tok)) * tokens
    if shape.kind == "prefill":
        tokens = b * s
        return (2 * n_mm + attn_fwd_per_tok + ssd_fwd_per_tok) * tokens
    # decode: context-length attention + recurrent SSD update
    attn_dec = 4 * s * cfg.n_heads * hd * n_attn if cfg.n_heads else 0
    ssd_dec = 6 * cfg.d_inner * cfg.ssm_state * cfg.n_layers \
        if cfg.has_ssm else 0
    return (2 * n_mm + attn_dec + ssd_dec) * b
