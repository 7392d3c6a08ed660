"""Successive-halving confirmation + shared step-cost store (PR 8).

Containment: the full-trace exact winner survives EVERY halving rung at
three seeded (model, trace) points x two objectives — the same way PR 4
pinned fluid screening.  Correctness of sharing: plans differing in any
cost-relevant coordinate (quant format, cluster device type) never share
a store bucket, and shared-store search results are bit-identical to
private-cache results.  Plus the satellites: LRU bounds with eviction
counters, the spawn-only ``fork_map`` fallback, and trace-prefix
statistics.
"""

import multiprocessing

import pytest

from repro.core import (ApexSearch, MultiFidelitySearch, SharedCostStore,
                        StepCostCache, TraceSummary, cost_fingerprint,
                        get_trace, h100_node, h200_node, ir_from_hf_config,
                        map_scheme, prefix_trace)
from repro.core.search import OBJECTIVES, fork_map

SMALL = dict(hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=4, intermediate_size=1024, vocab_size=1024)
MEDIUM = dict(hidden_size=512, num_hidden_layers=8, num_attention_heads=8,
              num_key_value_heads=4, intermediate_size=2048, vocab_size=4096)


def small_model():
    return ir_from_hf_config(SMALL, name="tiny")


def medium_model():
    return ir_from_hf_config(MEDIUM, name="tiny8")


# ---------------------------------------------------------------------------
# containment: the exact winner survives every rung
# ---------------------------------------------------------------------------

def _rung_containment_point(model, cluster, reqs, objective, **kw):
    """Exact full search vs halving multi-fidelity search: the exact
    winner's label must appear in every rung's promoted set and in the
    finalists, and the confirmed objective value must agree."""
    exact = ApexSearch(model, cluster).search(reqs, objective=objective,
                                              **kw)
    search = ApexSearch(model, cluster)
    mf = MultiFidelitySearch(search)
    mres = mf.search(reqs, objective=objective, **kw)
    assert mres.rungs, "seeded point must actually exercise the rungs"
    label = exact.best.plan_label
    labels_of = lambda idx: {mres.surrogate_reports[i].plan_label
                             for i in idx}
    for rung in mres.rungs:
        assert label in labels_of(rung.survivor_indices), (
            f"exact best {label} pruned at rung {rung.fraction:.0%} "
            f"({rung.evaluated} -> {rung.promoted})")
        assert rung.n_requests < len(reqs)
        assert rung.promoted <= rung.evaluated
        assert rung.seconds >= 0
    assert label in labels_of(mres.survivor_indices)
    key = OBJECTIVES[objective]
    assert key(mres.best) == pytest.approx(key(exact.best), rel=1e-9)
    return mres


@pytest.mark.parametrize("objective", ["latency", "throughput"])
def test_winner_survives_rungs_chat_menu(objective):
    """Seeded point 1: small model, chat load, joint hetero disagg."""
    reqs = get_trace("chat", arrival_rate=8.0, seed=0, num_requests=48)
    _rung_containment_point(
        small_model(), h100_node(8), reqs, objective,
        feasible_only=True, disaggregated=True, max_disagg_plans=32,
        pool_menu=[h100_node(4), h200_node(4)])


@pytest.mark.parametrize("objective", ["latency", "throughput"])
def test_winner_survives_rungs_heavy_summarization(objective):
    """Seeded point 2: deeper model, bursty summarization load."""
    reqs = get_trace("summarization", arrival_rate=100.0, seed=7,
                     num_requests=40)
    _rung_containment_point(
        medium_model(), h100_node(8), reqs, objective,
        feasible_only=True, disaggregated=True, max_disagg_plans=32)


@pytest.mark.parametrize("objective", ["latency", "throughput"])
def test_winner_survives_rungs_creation_menu(objective):
    """Seeded point 3: creation trace, colocated + hetero pool menu."""
    reqs = get_trace("creation", arrival_rate=4.0, seed=11,
                     num_requests=32)
    _rung_containment_point(
        small_model(), h100_node(8), reqs, objective,
        feasible_only=True, disaggregated=True, max_disagg_plans=24,
        pool_menu=[h100_node(4), h200_node(4)])


def test_halving_matches_no_halving_best():
    """The ladder and the cliff agree on the winner (the CI smoke
    assertion, pinned here at a seeded point)."""
    reqs = get_trace("chat", arrival_rate=8.0, seed=0, num_requests=48)
    mf = MultiFidelitySearch(ApexSearch(small_model(), h100_node(8)))
    kw = dict(feasible_only=True, disaggregated=True, max_disagg_plans=32,
              pool_menu=[h100_node(4), h200_node(4)])
    with_h = mf.search(reqs, **kw)
    without = mf.search(reqs, halving=False, **kw)
    assert with_h.best.plan_label == without.best.plan_label
    assert with_h.rungs and not without.rungs
    # the ladder runs the full trace for strictly fewer candidates
    assert with_h.num_survivors < without.num_survivors
    assert with_h.screen_survivors == without.num_survivors


def test_halving_jobs_equals_serial():
    """Forked rung evaluation (pre-seeded store snapshot in each worker)
    is bit-identical to serial."""
    reqs = get_trace("summarization", arrival_rate=100.0, seed=7,
                     num_requests=40)
    kw = dict(feasible_only=True, disaggregated=True, max_disagg_plans=32)
    serial = MultiFidelitySearch(
        ApexSearch(medium_model(), h100_node(8))).search(reqs, **kw)
    par = MultiFidelitySearch(
        ApexSearch(medium_model(), h100_node(8))).search(reqs, jobs=2,
                                                         **kw)
    assert par.survivor_indices == serial.survivor_indices
    assert [r.survivor_indices for r in par.rungs] == \
        [r.survivor_indices for r in serial.rungs]
    assert par.result.all_reports == serial.result.all_reports


def test_rung_fraction_validation():
    search = ApexSearch(small_model(), h100_node(4))
    with pytest.raises(ValueError):
        MultiFidelitySearch(search, rungs=(0.25, 1.0))
    with pytest.raises(ValueError):
        MultiFidelitySearch(search, rungs=(0.0,))
    with pytest.raises(ValueError):
        MultiFidelitySearch(search, promote_frac=0.0)


def test_tiny_trace_skips_rungs():
    """Prefixes below ``min_rung_requests`` are skipped — a 8-request
    trace ranks on noise at 25%."""
    reqs = get_trace("chat", arrival_rate=8.0, seed=0, num_requests=8)
    mf = MultiFidelitySearch(ApexSearch(small_model(), h100_node(8)),
                             min_rung_requests=8)
    mres = mf.search(reqs, feasible_only=True, disaggregated=True,
                     max_disagg_plans=32,
                     pool_menu=[h100_node(4), h200_node(4)])
    assert all(r.n_requests >= 8 for r in mres.rungs)
    assert all(r.fraction >= 0.5 for r in mres.rungs)


# ---------------------------------------------------------------------------
# fingerprint correctness: no collisions across cost-relevant coordinates
# ---------------------------------------------------------------------------

def _colocated_plan(search, quant="fp16", model_dp=None):
    cands, _ = search.candidates(quant=quant, feasible_only=True)
    schemes = [c[1] for c in cands]
    if model_dp is not None:
        schemes = [s for s in schemes if s.model_dp == model_dp]
    return map_scheme(schemes[0], search.cluster)


def test_fingerprint_distinguishes_quant():
    """Two plans differing ONLY in quant format never share a bucket."""
    search = ApexSearch(small_model(), h100_node(4))
    fp16 = _colocated_plan(search, quant="fp16")
    w8a8 = _colocated_plan(search, quant="w8a8")
    f1 = cost_fingerprint(fp16, search.store, search.coll)
    f2 = cost_fingerprint(w8a8, search.store, search.coll)
    assert f1 != f2


def test_fingerprint_distinguishes_device_type():
    """Same scheme mapped onto H100 vs H200 clusters keys differently."""
    model = small_model()
    s100 = ApexSearch(model, h100_node(4))
    s200 = ApexSearch(model, h200_node(4))
    p100 = _colocated_plan(s100)
    p200 = _colocated_plan(s200)
    assert p100.scheme == p200.scheme     # truly only the cluster differs
    f100 = cost_fingerprint(p100, s100.store, s100.coll)
    f200 = cost_fingerprint(p200, s200.store, s200.coll)
    assert f100 != f200


def test_fingerprint_shares_across_model_dp():
    """Replicas of one layout run identical iterations, so DP widths of
    the same per-stage scheme SHARE a bucket — the cross-plan win (e.g.
    a disagg pool running the same 1-device layout at DP4 and DP8)."""
    import dataclasses

    search = ApexSearch(small_model(), h100_node(4))
    cands, _ = search.candidates(feasible_only=True)
    scheme = next(c[1] for c in cands if c[1].model_dp >= 2)
    narrower = dataclasses.replace(scheme, model_dp=scheme.model_dp // 2)
    wide = map_scheme(scheme, search.cluster)
    narrow = map_scheme(narrower, search.cluster)
    f_wide = cost_fingerprint(wide, search.store, search.coll)
    f_narrow = cost_fingerprint(narrow, search.store, search.coll)
    assert f_wide == f_narrow


def test_adversarial_quant_store_isolation():
    """Drive two same-shape searches differing only in quant through ONE
    shared store: per-quant tables must stay disjoint, so every report
    matches its private-cache twin bit-for-bit."""
    model = small_model()
    reqs = get_trace("chat", arrival_rate=4.0, seed=3, num_requests=24)
    shared = ApexSearch(model, h100_node(4))
    private = ApexSearch(model, h100_node(4), share_step_costs=False)
    for quant in ("fp16", "w8a8"):
        rs = shared.search(reqs, quant=quant, feasible_only=True)
        rp = private.search(reqs, quant=quant, feasible_only=True)
        assert rs.all_reports == rp.all_reports, quant
    # and the store actually kept them apart
    quants = {fp[3] for fp in shared.cost_store.tables}
    assert quants == {"fp16", "w8a8"}


def test_shared_store_bit_identical_joint_search():
    """The headline guarantee: a joint colocated+hetero-disagg search
    with the shared store returns byte-identical reports to the
    private-cache search."""
    model = small_model()
    reqs = get_trace("creation", arrival_rate=4.0, seed=11,
                     num_requests=24)
    kw = dict(objective="latency", feasible_only=True, disaggregated=True,
              max_disagg_plans=24, pool_menu=[h100_node(4), h200_node(4)])
    rs = ApexSearch(model, h100_node(8)).search(reqs, **kw)
    rp = ApexSearch(model, h100_node(8),
                    share_step_costs=False).search(reqs, **kw)
    assert rs.all_reports == rp.all_reports
    assert rs.best == rp.best
    # sharing must HELP: strictly more hits than the private caches
    assert rs.cache_hits > rp.cache_hits


# ---------------------------------------------------------------------------
# LRU bound + eviction counters
# ---------------------------------------------------------------------------

def test_step_cost_cache_lru_bound():
    from repro.core.ir import Workload
    calls = []

    def cost(w):
        calls.append(w.prefill_tokens)
        return float(w.prefill_tokens), 0.0

    cache = StepCostCache(cost, maxsize=4)
    for t in range(1, 9):
        cache.cost(Workload(prefill_tokens=t, batch_sequences=1))
    st = cache.stats()
    assert st["entries"] == 4
    assert st["evictions"] == 4
    assert st["misses"] == 8 and st["hits"] == 0
    # the four youngest survive; re-asking an evicted key re-prices it
    cache.cost(Workload(prefill_tokens=8, batch_sequences=1))
    assert cache.stats()["hits"] == 1
    cache.cost(Workload(prefill_tokens=1, batch_sequences=1))
    assert cache.stats()["misses"] == 9


def test_step_cost_cache_lru_recency():
    from repro.core.ir import Workload

    cache = StepCostCache(lambda w: (1.0, 0.0), maxsize=2)
    w1, w2, w3 = (Workload(prefill_tokens=t, batch_sequences=1)
                  for t in (1, 2, 3))
    cache.cost(w1)
    cache.cost(w2)
    cache.cost(w1)          # refresh w1 — w2 becomes the LRU victim
    cache.cost(w3)
    assert w1.signature() in cache.table
    assert w2.signature() not in cache.table


def test_shared_store_stats_and_eviction_rollup():
    store = SharedCostStore(maxsize=2)
    c = store.cache(("fp",), lambda w: (1.0, 0.0))
    from repro.core.ir import Workload
    for t in (1, 2, 3):
        c.cost(Workload(prefill_tokens=t, batch_sequences=1))
    st = store.stats()
    assert st == {"tables": 1, "entries": 2, "evictions": 1}
    assert c.stats()["evictions"] == 1
    # a second view on the same fingerprint sees the shared entries
    c2 = store.cache(("fp",), lambda w: (1.0, 0.0))
    c2.cost(Workload(prefill_tokens=3, batch_sequences=1))
    assert c2.stats() == {"hits": 1, "misses": 0, "entries": 2,
                          "evictions": 1}


# ---------------------------------------------------------------------------
# spawn-only platforms fall back to serial with a warning
# ---------------------------------------------------------------------------

def test_fork_map_spawn_only_falls_back_serial(monkeypatch):
    monkeypatch.setattr(multiprocessing, "get_all_start_methods",
                        lambda: ["spawn"])
    with pytest.warns(RuntimeWarning, match="fork"):
        out = fork_map(lambda i: i * i, 6, jobs=3)
    assert out == [i * i for i in range(6)]


def test_fork_map_refuses_to_fork_a_process_holding_the_chip(monkeypatch):
    """Forked workers would inherit the parent's accelerator client: once
    a non-CPU backend is up, jobs>1 raises; jobs=1 still runs serially."""
    import repro.core.search as search_mod
    monkeypatch.setattr(search_mod, "_holds_accelerator", lambda: True)
    with pytest.raises(RuntimeError, match="inherit the chip"):
        fork_map(lambda i: i, 4, jobs=2)
    assert fork_map(lambda i: i + 1, 3, jobs=1) == [1, 2, 3]


def test_fork_map_spawn_only_search_still_works(monkeypatch):
    monkeypatch.setattr(multiprocessing, "get_all_start_methods",
                        lambda: ["spawn"])
    search = ApexSearch(small_model(), h100_node(4))
    reqs = get_trace("chat", arrival_rate=4.0, seed=0, num_requests=12)
    with pytest.warns(RuntimeWarning):
        res = search.search(reqs, feasible_only=True, jobs=4)
    assert res.best.feasible


# ---------------------------------------------------------------------------
# trace prefixes preserve arrival statistics
# ---------------------------------------------------------------------------

def test_prefix_trace_properties():
    reqs = get_trace("chat", arrival_rate=8.0, seed=0, num_requests=64)
    pre = prefix_trace(reqs, 0.25)
    assert len(pre) == 16
    # a count-prefix keeps absolute arrivals of the earliest requests
    ordered = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    assert pre == ordered[:16]
    assert prefix_trace(reqs, 1.0) == ordered
    assert prefix_trace(reqs, 2.0) == ordered
    assert len(prefix_trace(reqs, 1e-9)) == 1
    with pytest.raises(ValueError):
        prefix_trace(reqs, 0.0)


def test_prefix_trace_preserves_arrival_rate():
    """Poisson prefix: the empirical rate of the first quarter matches
    the full trace's rate (same process, shorter window)."""
    reqs = get_trace("chat", arrival_rate=16.0, seed=1, num_requests=400)
    full = TraceSummary.of(reqs)
    quarter = TraceSummary.of(prefix_trace(reqs, 0.25))
    assert quarter.arrival_rate == pytest.approx(full.arrival_rate,
                                                 rel=0.25)
    assert quarter.ctx_mean == pytest.approx(full.ctx_mean, rel=0.35)


def test_of_prefixes_matches_pointwise():
    reqs = get_trace("chat", arrival_rate=8.0, seed=0, num_requests=64)
    summaries = TraceSummary.of_prefixes(reqs, (0.25, 0.5))
    assert set(summaries) == {0.25, 0.5, 1.0}
    ordered = sorted(reqs, key=lambda r: (r.arrival, r.rid))
    for f in (0.25, 0.5, 1.0):
        assert summaries[f] == TraceSummary.of(prefix_trace(ordered, f,
                                                            presorted=True))
