"""repro.tune — cost-model-driven autotuning for kernel/dispatch config.

Two stages (see ``tuner``): an analytical ``KernelCostModel`` ranks
candidate configurations per (backend, metric, dtype, shape bucket:
``bucket_key``); a short measured search optionally refines the top candidates,
with winners persisted in a versioned JSON ``TuningTable`` (shipped
defaults under ``tables/``) behind a per-process LRU (``cache``).

The engine consults this package end-to-end: ``choose_impl`` ranks the
in-core impls, ``resolve_blocks`` fills kernel blocks, the chunked and
sharded paths take ``chunk`` / ``n_micro`` from the same oracle, and
``Router.warmup`` pre-tunes declared buckets (``pretune_request``).
Explicit caller kwargs always win, and tuning is bitwise-safe by
construction: every knob it sets is one the engine's invariance tests
already prove cannot change int32 results — tuning changes speed, never
answers.  ``tune='off'`` keeps the legacy hand-tuned constants
everywhere.

``DispatchDecision`` is the observability record ``engine.sdtw(...,
explain=True)`` returns next to the result (and bench rows carry as a
``decision`` field): which impl/config won, why (model score vs table
hit vs explicit override), and the ranked alternatives.
"""
from __future__ import annotations

import dataclasses

from .cache import cache_info, cache_keys, clear_tuning_cache
from .cost import (KernelCostModel, TunedConfig, bucket_key,
                   get_cost_model, tuned_n_micro)
from .table import TuningTable, default_table, reset_tables
from .tuner import (Resolution, canonical_backend, measured_search,
                    pretune_request, rank_incore, record_table, resolve,
                    resolve_n_micro, tuned_blocks, tuned_chunk)

__all__ = [
    "DispatchDecision", "KernelCostModel", "Resolution", "TunedConfig",
    "TuningTable", "bucket_key", "cache_info", "cache_keys",
    "canonical_backend", "clear_tuning_cache", "default_table",
    "get_cost_model", "measured_search", "pretune_request", "rank_incore",
    "record_table", "reset_tables", "resolve", "resolve_n_micro",
    "tuned_blocks", "tuned_chunk", "tuned_n_micro",
]


@dataclasses.dataclass(frozen=True)
class DispatchDecision:
    """Why the engine ran what it ran — the ``explain=True`` payload.

    ``source`` taxonomy: ``'explicit'`` (caller forced the impl),
    ``'structural'`` (a hard dispatch rule — mesh/top-K/chunk/TPU/memory
    bound — fired before any scoring), ``'legacy'`` (``tune='off'``
    heuristics), ``'model'`` (cost-model ranking), ``'table:model'`` /
    ``'table:measured'`` / ``'table:default'`` (tuning-table hit,
    suffixed with the entry's own provenance), ``'measured'`` (fresh
    measured search this call).  ``config`` holds the resolved knobs the
    chosen path actually received (only the relevant ones);
    ``candidates`` is the model's ranked impl scoring when one ran.
    """
    impl: str
    source: str
    reason: str
    config: dict = dataclasses.field(default_factory=dict)
    score_us: float | None = None
    candidates: tuple = ()

    def token(self) -> str:
        """Compact ``source:impl`` form for bench-row derived fields."""
        return f"{self.source}:{self.impl}"
