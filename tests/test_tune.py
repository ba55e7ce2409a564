"""The autotuner: table persistence, oracle correctness, bitwise safety.

Three contracts under test:

  1. **Persistence** — ``TuningTable`` survives a save/load round trip,
     rejects wrong schema versions and corrupt files by *degrading to
     empty with a warning* (a broken table must never take the engine
     down), and drops malformed entries individually.
  2. **Oracle** — resolution precedence (explicit kwargs > table >
     model), the LRU in front of it, ``choose_impl``'s model ranking vs
     its legacy rules, and the cost model's ranking agreement with the
     committed measured baseline (the same gate CI runs via
     ``repro.tune.validate``).
  3. **Bitwise safety** — every knob the tuner sets (impl, blocks, scan
     scheme, chunk, n_micro) is speed-only: tuned results are
     bitwise-identical (int32) to ``tune='off'`` across impl × metric ×
     spans × top-K.
"""
import json
import os
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import choose_impl, choose_impl_explained, sdtw
from repro.kernels.sdtw import resolve_blocks
from repro.tune import (DispatchDecision, KernelCostModel, TunedConfig,
                        TuningTable, bucket_key, cache_info, cache_keys,
                        clear_tuning_cache, default_table, get_cost_model,
                        pretune_request, resolve, resolve_n_micro,
                        tuned_blocks, tuned_chunk, tuned_n_micro)
from repro.tune.validate import validate_ranking

BASELINE = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_baseline.json")


@pytest.fixture(autouse=True)
def _fresh_lru():
    clear_tuning_cache()
    yield
    clear_tuning_cache()


# ---------------------------------------------------------------------------
# 1. TuningTable persistence
# ---------------------------------------------------------------------------

def test_table_round_trip(tmp_path):
    t = TuningTable("interpret", provenance="test")
    key = bucket_key("interpret", "abs_diff", "int32", 4, 32, 1024)
    cfg = TunedConfig(impl="wavefront", block_q=4, block_m=512,
                      scan_scheme="assoc", row_tile=1, chunk=8192,
                      score_us=123.0, source="measured")
    t.put(key, cfg)
    path = str(tmp_path / "t.json")
    t.save(path)
    back = TuningTable.load(path, "interpret")
    assert len(back) == 1 and key in back
    assert back.get(key) == cfg
    assert back.provenance == "test"


def test_table_missing_file_is_empty(tmp_path):
    t = TuningTable.load(str(tmp_path / "nope.json"), "interpret")
    assert len(t) == 0


def test_table_wrong_schema_recovers(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump({"schema": "repro.tune/v999", "backend": "interpret",
                   "entries": {}}, f)
    with pytest.warns(UserWarning, match="schema"):
        t = TuningTable.load(path, "interpret")
    assert len(t) == 0


def test_table_corrupt_json_recovers(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        f.write("{not json at all")
    with pytest.warns(UserWarning):
        t = TuningTable.load(path, "interpret")
    assert len(t) == 0


def test_table_malformed_entry_dropped(tmp_path):
    good_key = bucket_key("interpret", "abs_diff", "int32", 2, 16, 256)
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump({"schema": "repro.tune/v1", "backend": "interpret",
                   "entries": {good_key: {"impl": "wavefront"},
                               "bad": "not a dict"}}, f)
    with pytest.warns(UserWarning, match="entr"):
        t = TuningTable.load(path, "interpret")
    assert len(t) == 1
    assert t.get(good_key).impl == "wavefront"


def test_tuned_config_json_round_trip():
    cfg = TunedConfig(impl="pallas", block_q=8, block_m=512,
                      scan_scheme="shift", row_tile=8, source="model")
    assert TunedConfig.from_json(cfg.to_json()) == cfg
    # None fields are omitted on the wire and restored as None
    assert "chunk" not in cfg.to_json()


def test_shipped_tables_load():
    for backend in ("interpret", "tpu"):
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # no warning allowed
            t = default_table(backend)
        assert len(t) > 0, backend
        for key in t.keys():
            assert key.startswith(backend + "/")


# ---------------------------------------------------------------------------
# 2. The oracle
# ---------------------------------------------------------------------------

def test_lru_caches_resolutions():
    resolve(4, 32, 1024, backend="interpret")
    info0 = cache_info()
    resolve(4, 32, 1024, backend="interpret")       # same bucket -> hit
    resolve(3, 20, 600, backend="interpret")        # same pow-2 bucket
    info1 = cache_info()
    assert info1["hits"] >= info0["hits"] + 2
    assert info1["misses"] == info0["misses"]


def test_resolution_precedence_explicit_wins():
    # Table entry exists for this bucket (shipped) — explicit still wins.
    bq, bm, scheme, rt = resolve_blocks(4, 16384, 16, 256, "shift", 2,
                                        True, n=32, tune="model")
    assert (bq, bm, scheme, rt) == (16, 256, "shift", 2)
    # Unset knobs come from the oracle, not the legacy fill.
    auto = resolve_blocks(4, 16384, None, None, None, None, True,
                          n=32, tune="model")
    entry = default_table("interpret").get(
        bucket_key("interpret", "abs_diff", "int32", 4, 32, 16384))
    if entry is not None:                       # shipped table covers it
        assert auto == (entry.block_q, entry.block_m, entry.scan_scheme,
                        entry.row_tile)


def test_tune_off_keeps_legacy_blocks():
    legacy = resolve_blocks(4, 16384, None, None, None, None, True)
    off = resolve_blocks(4, 16384, None, None, None, None, True,
                         n=32, tune="off")
    assert legacy == off


def test_choose_impl_legacy_pins():
    # tune defaults to 'off' here: the legacy rules stay bit-for-bit.
    assert choose_impl(4, 32, 4096, backend="cpu") == "rowscan"
    assert choose_impl(4, 32, 60, backend="cpu") == "wavefront"
    assert choose_impl(4, 32, 1 << 18, backend="cpu") == "chunked"


def test_choose_impl_model_ranks_incore():
    impl, source, reason, cands = choose_impl_explained(
        4, 32, 4096, backend="cpu", tune="model")
    assert impl in ("rowscan", "wavefront")
    assert source in ("model", "table:model", "table:measured",
                      "table:default", "measured")
    assert cands, "model ranking should be attached"
    if source == "model":
        assert impl == cands[0][0]
    # structural rules stay ahead of the model
    assert choose_impl(4, 32, 4096, backend="cpu", tune="model",
                       chunk=1024) == "chunked"
    assert choose_impl(4, 32, 1 << 18, backend="cpu",
                       tune="model") == "chunked"
    assert choose_impl(4, 32, 4096, backend="tpu", tune="model") == "pallas"


def test_model_ranking_agrees_with_committed_baseline():
    """The same gate CI runs: pairwise ranking agreement between the
    analytical model and the committed measured rows."""
    with open(BASELINE) as f:
        rows = json.load(f)
    agree, total, report = validate_ranking(rows, backend="interpret")
    assert total >= 3, "bench row names drifted away from the validators"
    frac = agree / total
    assert frac >= 0.6, "\n".join(report)


def test_cost_model_oracle_sanity():
    model = get_cost_model("interpret")
    # best_chunk is a real candidate
    assert model.best_chunk(4, 32, 1 << 18) in \
        KernelCostModel.CHUNK_CANDIDATES
    # best_pallas respects the VMEM budget (plain and span mode)
    for span in (False, True):
        cfg = model.best_pallas(8, 64, 4096, span=span)
        assert model.vmem_words(cfg.block_q, cfg.block_m, 64, span) \
            <= model.backend.vmem_budget_words
    # span working set is strictly larger
    assert model.vmem_words(8, 512, 64, True) > \
        model.vmem_words(8, 512, 64, False)
    # tuned_chunk comes from the candidate ladder
    assert tuned_chunk(4, 32, 1 << 18, backend="interpret") in \
        KernelCostModel.CHUNK_CANDIDATES
    # n_micro default mirrors the schedule's pipeline fill
    assert resolve_n_micro(16, 2, 4, n=32, m=1024,
                           backend="interpret") == tuned_n_micro(16, 2, 4)
    assert tuned_n_micro(16, 2, 4) == max(1, min(4, -(-16 // 2)))


def test_pretune_primes_the_lru():
    from repro.core.request import SdtwRequest
    rng = np.random.default_rng(0)
    qs = [rng.integers(-50, 50, (L,)).astype(np.int32)
          for L in (10, 33, 70)]
    ref = rng.integers(-50, 50, (512,)).astype(np.int32)
    req = SdtwRequest(queries=qs, reference=ref)
    n = pretune_request(req)
    assert n == 3                      # three pow-2 buckets
    assert len(cache_keys()) >= 3
    # tune='off' requests prime nothing
    clear_tuning_cache()
    assert pretune_request(SdtwRequest(queries=qs, reference=ref,
                                       tune="off")) == 0
    assert len(cache_keys()) == 0


# ---------------------------------------------------------------------------
# 3. Bitwise safety + explain
# ---------------------------------------------------------------------------

def _mk(rng, nq=3, n=24, m=700):
    q = jnp.asarray(rng.integers(-60, 60, (nq, n)).astype(np.int32))
    r = jnp.asarray(rng.integers(-60, 60, (m,)).astype(np.int32))
    return q, r


@pytest.mark.parametrize("metric", ["abs_diff", "square_diff"])
@pytest.mark.parametrize("impl", ["auto", "rowscan", "wavefront",
                                  "pallas", "chunked"])
def test_tuned_bitwise_invariance(rng, metric, impl):
    """tune='model' vs tune='off' across impl x metric: identical int32
    results on every execution path."""
    q, r = _mk(rng)
    kw = dict(metric=metric, impl=impl)
    if impl == "chunked":
        kw["chunk"] = 128
    a = np.asarray(sdtw(q, r, tune="off", **kw))
    b = np.asarray(sdtw(q, r, tune="model", **kw))
    np.testing.assert_array_equal(a, b)


def test_tuned_bitwise_spans_and_topk(rng):
    q, r = _mk(rng, m=2048)
    for kw in (dict(return_spans=True),
               dict(return_positions=True),
               dict(top_k=3, chunk=256),
               dict(top_k=2, chunk=256, return_spans=True,
                    excl_mode="span")):
        a = sdtw(q, r, tune="off", **kw)
        b = sdtw(q, r, tune="model", **kw)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_tuned_bitwise_ragged(rng):
    qs = [np.asarray(q) for q in
          (rng.integers(-60, 60, 10), rng.integers(-60, 60, 33),
           rng.integers(-60, 60, 70))]
    qs = [q.astype(np.int32) for q in qs]
    r = jnp.asarray(rng.integers(-60, 60, 700).astype(np.int32))
    a = np.asarray(sdtw(qs, r, tune="off"))
    b = np.asarray(sdtw(qs, r, tune="model"))
    np.testing.assert_array_equal(a, b)


def test_explain_decision_contents(rng):
    q, r = _mk(rng)
    out, dec = sdtw(q, r, explain=True)
    assert isinstance(dec, DispatchDecision)
    assert dec.impl in ("rowscan", "wavefront")
    assert dec.source in ("model", "table:model", "table:measured",
                          "table:default")
    assert ":" in dec.token() and dec.token().endswith(dec.impl)
    assert dec.candidates, "in-core ranking should be attached"
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(sdtw(q, r)))
    # forced impl -> explicit source, no candidates
    _, dec2 = sdtw(q, r, impl="rowscan", explain=True)
    assert (dec2.impl, dec2.source) == ("rowscan", "explicit")
    # chunked decision reports the tuned chunk
    _, dec3 = sdtw(q, jnp.asarray(
        np.tile(np.asarray(r), 400)[: 1 << 18]), explain=True)
    assert dec3.impl == "chunked" and dec3.config.get("chunk") >= 4096
    # pallas decision reports the resolved block config
    _, dec4 = sdtw(q, r, impl="pallas", explain=True)
    assert set(dec4.config) >= {"block_q", "block_m", "scan_scheme"}
    # ragged lists cannot be explained
    with pytest.raises(ValueError, match="ragged"):
        sdtw([np.asarray(q)[0]], r, explain=True)


def test_explain_reports_the_streamed_launch_blocks(monkeypatch, rng):
    """Past ``PALLAS_FUSED_MAX`` the kernel launches one ``chunk`` slice
    at a time, and ``explain`` reports the blocks of that launch."""
    from repro.core import engine
    monkeypatch.setattr(engine, "PALLAS_FUSED_MAX", 256)
    q, r = _mk(rng, nq=3, n=16, m=700)
    out, dec = sdtw(q, r, impl="pallas", chunk=128, explain=True)
    want = resolve_blocks(3, 128, None, None, None, None, True, n=16,
                          tune="model")
    got = tuple(dec.config[k] for k in ("block_q", "block_m",
                                        "scan_scheme", "row_tile"))
    assert got == want
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(sdtw(q, r, tune="off")))


def test_explain_rejected_by_serve():
    from repro.core.request import SdtwRequest
    from repro.serve import Router
    rng = np.random.default_rng(0)
    q = rng.integers(-50, 50, (2, 16)).astype(np.int32)
    r = rng.integers(-50, 50, (256,)).astype(np.int32)
    with Router(auto_dispatch=False) as router:
        with pytest.raises(ValueError, match="explain"):
            router.submit(SdtwRequest(queries=q, reference=r,
                                      explain=True))


def test_tune_validated_at_the_door():
    with pytest.raises(ValueError, match="tune must be one of"):
        sdtw(np.zeros((1, 4), np.int32), np.zeros(8, np.int32),
             tune="bogus")


def test_router_warmup_pretunes(rng):
    from repro.serve import Router
    q, r = _mk(rng, nq=2, n=16, m=256)
    with Router(auto_dispatch=False) as router:
        router.warmup(queries=np.asarray(q), reference=np.asarray(r))
        assert len(cache_keys()) >= 1
        fut = router.submit(queries=np.asarray(q), reference=np.asarray(r))
        router.drain()
        np.testing.assert_array_equal(
            np.asarray(fut.result()),
            np.asarray(sdtw(q, r, tune="off")))


# ---------------------------------------------------------------------------
# 4. The compiled kernel's price: tall blocks for batches
# ---------------------------------------------------------------------------

#: Table V query length -> reference length (Seismology, Human, ECG, Power).
TABLE_V = {64: 1_727_990, 120: 7_997, 512: 1_800_000, 1536: 1_754_985}


def _tpu_model():
    from repro.core.platforms import TPU_V5E_BACKEND
    return KernelCostModel(TPU_V5E_BACKEND)


def _bucket(nq, n):
    """The pow-2 bucket the oracle prices a (nq, n, Table V M) call at."""
    from repro.tune.cost import _pow2_bucket
    return _pow2_bucket(nq), _pow2_bucket(n), _pow2_bucket(TABLE_V[n])


@pytest.mark.parametrize("span", [False, True])
@pytest.mark.parametrize("nq", [64, 128, 16384])
@pytest.mark.parametrize("n", sorted(TABLE_V))
def test_tpu_batches_get_tall_blocks(n, nq, span):
    cfg = _tpu_model().best_pallas(*_bucket(nq, n), span=span)
    assert cfg.block_q >= 32, cfg
    assert cfg.scan_scheme == "shift"


@pytest.mark.parametrize("span", [False, True])
@pytest.mark.parametrize("nq", [1, 3, 8])
@pytest.mark.parametrize("n", sorted(TABLE_V))
def test_tpu_small_batches_keep_the_sublane_block(n, nq, span):
    for (bq, _, _, _), _ in _tpu_model().pallas_candidates(
            *_bucket(nq, n), span=span):
        assert bq == 8


@pytest.mark.parametrize("lastrow", [False, True])
@pytest.mark.parametrize("span", [False, True])
@pytest.mark.parametrize("n", sorted(TABLE_V))
def test_tpu_candidates_fit_vmem(n, span, lastrow):
    model = _tpu_model()
    budget = model.backend.vmem_budget_words
    for nq in (1, 128, 16384):
        cands = model.pallas_candidates(*_bucket(nq, n), span=span,
                                        lastrow=lastrow)
        assert cands
        for (bq, bm, _, _), _ in cands:
            assert model.vmem_words(bq, bm, n, span, lastrow) <= budget
    # the last-row output block is counted
    assert model.vmem_words(128, 512, n, span, True) > \
        model.vmem_words(128, 512, n, span, False)


@pytest.mark.parametrize("shape,span,top", [
    ((8, 64, 4096), False, ((4, 512, "assoc", 1), 358787.104)),
    ((8, 64, 4096), True, ((4, 512, "assoc", 1), 358787.104)),
    ((4, 32, 16384), False, ((4, 512, "assoc", 1), 361189.152)),
    ((4, 32, 1024), False, ((4, 512, "assoc", 1), 23043.072)),
    ((2, 16, 256), False, ((2, 256, "assoc", 1), 2237.6416)),
    ((3, 24, 700), False, ((3, 256, "assoc", 1), 10855.6743237184)),
    ((1, 120, 8192), False, ((1, 2048, "assoc", 1), 167922.8544)),
])
def test_interpret_price_unchanged(shape, span, top):
    """The interpret-mode price is the one ``repro.tune.validate`` gates
    on: its picks and scores are pinned."""
    (cfg, us), = get_cost_model("interpret").pallas_candidates(
        *shape, span=span)[:1]
    assert cfg == top[0]
    assert us == pytest.approx(top[1], rel=1e-12)


# ---------------------------------------------------------------------------
# 5. The TPU reference bucket: whole 128-lane vregs up to the 4096 rung
# ---------------------------------------------------------------------------

@pytest.fixture
def tpu_oracle(monkeypatch):
    """``resolve(..., backend='tpu')`` on the CPU, with the v5e's cost
    constants; the autouse fixture empties the LRU around it."""
    from repro.core import platforms
    from repro.tune import cost
    monkeypatch.setattr(platforms, "tpu_device_kind", lambda: "TPU v5 lite")
    monkeypatch.setattr(cost, "_MODELS", {})


@pytest.mark.parametrize("m,tpu,interpret", [
    (100, 128, 128), (256, 256, 256), (360, 384, 512), (500, 512, 512),
    (700, 768, 1024), (4096, 4096, 4096), (4097, 8192, 8192),
    (7997, 8192, 8192), (262144, 262144, 262144)])
def test_reference_bucket(m, tpu, interpret):
    from repro.tune.cost import m_bucket
    assert m_bucket(m, "tpu") == tpu
    assert m_bucket(m, "interpret") == interpret


def test_tpu_tick_ranks_a_384_lane_block_first(tpu_oracle):
    """The monitoring tick's call — 1,024 streams x 16 templates of 512
    against a 360-sample tile, last row captured, per-row reference — is
    priced at 384 lanes, and a 384-wide block ranks first."""
    cands = _tpu_model().pallas_candidates(16384, 512, 384, lastrow=True,
                                           rowref=True)
    assert {bm for (_, bm, _, _), _ in cands} == {256, 384}
    assert cands[0][0][1] == 384
    cfg = resolve(16384, 512, 360, backend="tpu", lastrow=True,
                  rowref=True).config
    assert (cfg.block_q, cfg.block_m, cfg.scan_scheme, cfg.row_tile) == \
        (128, 384, "shift", 2)


@pytest.mark.parametrize("shape,kw,pick", [
    ((128, 512, 262144), dict(span=True), (128, 512, "shift", 2)),
    ((16384, 120, 7997), {}, (128, 512, "shift", 2)),
    ((1, 120, 7997), {}, (8, 4096, "shift", 2)),
])
def test_tpu_cell_picks_unchanged(tpu_oracle, shape, kw, pick):
    """ecg-offline-spans, human-batch and the served cells: references
    past the 4096 rung keep their power-of-two bucket and the blocks the
    row-chain price picked for them."""
    assert tuned_blocks(shape[0], shape[2], n=shape[1], backend="tpu",
                        **kw) == pick


def test_tpu_picks_for_360_and_500_are_not_shared(tpu_oracle):
    a = resolve(16384, 512, 360, backend="tpu", lastrow=True, rowref=True)
    b = resolve(16384, 512, 500, backend="tpu", lastrow=True, rowref=True)
    assert (a.config.block_m, b.config.block_m) == (384, 512)
    keys = {k[0] for k in cache_keys()}
    assert {bucket_key("tpu", "abs_diff", "int32", 16384, 512, 360),
            bucket_key("tpu", "abs_diff", "int32", 16384, 512, 500)} <= keys
    assert len(keys) == 2
    # interpret mode keeps one power-of-two bucket for both
    assert bucket_key("interpret", "abs_diff", "int32", 1, 8, 360) == \
        bucket_key("interpret", "abs_diff", "int32", 1, 8, 500)


@pytest.mark.parametrize("span", [False, True])
@pytest.mark.parametrize("block_q", [8, 128])
def test_row_chain_prices_the_ceiling_of_the_scan_steps(block_q, span):
    """Power-of-two blocks keep the price they had (log2 steps); a
    384-lane block pays 9 shift steps, as its scan runs."""
    import math
    model = _tpu_model()
    c = model.backend.pallas_price

    def price(bm, steps):
        lat = c.lat_fixed_us + c.lat_step_us * steps
        work = c.vreg_step_us * (model.block_vregs(block_q, bm) * steps
                                 + c.pick_vreg_steps * -(-block_q // 8))
        if span:
            lat, work = lat * c.span_lat_mult, work * c.span_work_mult
        return max(lat, work)

    for bm in (128, 256, 512, 1024, 2048, 4096):
        assert model.row_chain_us(block_q, bm, span) == \
            price(bm, math.log2(bm))
    assert model.row_chain_us(block_q, 384, span) == price(384, 9)
