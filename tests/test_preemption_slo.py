"""Multi-tenant SLO classes, the preemption-policy menu, and goodput.

Three layers under test:

  * GOLDEN PIN — ``preemption="sacrifice"`` with a single (retagged)
    tenant class is the pre-menu engine, bit for bit: every colocated
    and disagg case in ``tests/golden/core_golden.json`` must reproduce
    exactly even though the eviction path now routes through
    ``PreemptionPolicy`` and every record carries an ``SLOClass``.
  * MECHANICS — the victim orders (``recent-first`` vs
    ``lowest-priority-first``) and mechanisms (``sacrifice`` vs
    ``swap``) behave as advertised on seeded traces: priority eviction
    shields the high-priority tenant's TTFT p95, swap preserves decode
    progress (faster drain, balanced swap-out/swap-in counters, no
    decode-role re-fetch, first token never re-stamped).
  * GOODPUT — ``search(objective="goodput")`` ranks by per-class SLO
    attainment through both the exact and multi-fidelity paths, and the
    fluid screen's survivor frontier contains the exact winner on a
    seeded two-class trace.
"""

import json
import math
import os

import pytest

from repro.core import (ApexSearch, CollectiveModel, MultiFidelitySearch,
                        ProfileStore, SLOClass, generate_schemes, get_trace,
                        h100_node, ir_from_hf_config, make_preemption,
                        map_scheme, mixed_trace)
from repro.core.batching import BatchingModule, BatchingPolicy
from repro.core.engine import PreemptionPolicy, SacrificePolicy, SwapPolicy
from repro.core.metrics import p95
from repro.core.profiles import AnalyticBackend
from repro.core.simulator import PlanSimulator
from repro.core.trace import Request
from repro.disagg import DisaggSimulator, generate_disagg_schemes, \
    map_disagg_scheme

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "core_golden.json")
SMALL = dict(hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=4, intermediate_size=1024, vocab_size=1024)

POLICIES = {
    "continuous": BatchingPolicy(),
    "chunked": BatchingPolicy(chunked_prefill=128),
    "static": BatchingPolicy(mode="static", max_batch_size=8),
    "capped": BatchingPolicy(max_batch_size=4, fast_forward=False),
}

# a single tenant class with a nonzero priority and no targets: retagging
# the whole trace with it must not move a single float
ONE_TENANT = [SLOClass(name="tenant", priority=3)]


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ctx():
    model = ir_from_hf_config(SMALL, name="tiny")
    cluster = h100_node(8)
    return model, cluster, ProfileStore(AnalyticBackend(cluster)), \
        CollectiveModel(cluster)


def _colocated_scheme(model, dp):
    for s in generate_schemes(model, 8, quant="fp16"):
        if (s.model_dp == dp and s.pp_stages == 1
                and s.is_feasible_for_current_systems()):
            return s
    raise RuntimeError("no scheme")


def _disagg_scheme(model, cluster, mode):
    for s in generate_disagg_schemes(model, cluster, max_plans=100000,
                                     transfer_mode=mode):
        if (s.prefill_devices == 4 and s.decode_devices == 4
                and s.prefill.model_dp == 1 and s.decode.model_dp == 1
                and s.prefill.pp_stages == 1 and s.decode.pp_stages == 1):
            return s
    raise RuntimeError("no disagg scheme")


def const_cost(per_token=1e-3, per_iter=5e-3):
    def step_cost(w):
        t = per_iter + per_token * w.total_tokens
        return t, t * 100.0
    return step_cost


def mk_requests(specs, slo=None):
    kw = {"slo_class": slo} if slo is not None else {}
    return [Request(rid=i, arrival=a, context_len=c, gen_len=g, **kw)
            for i, (a, c, g) in enumerate(specs)]


# ---------------------------------------------------------------------------
# golden pin: explicit sacrifice + a single class == the frozen engine
# ---------------------------------------------------------------------------

def test_sacrifice_single_class_matches_colocated_goldens(golden, ctx, report_matches):
    model, cluster, store, coll = ctx
    plans = {dp: map_scheme(_colocated_scheme(model, dp), cluster)
             for dp in (1, 2)}
    for case in golden["colocated"]:
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = PlanSimulator(plans[case["dp"]], store, coll)
        rep = sim.simulate(reqs, policy=POLICIES[case["policy"]],
                           keep_records=True, preemption="sacrifice",
                           slo_classes=ONE_TENANT)
        report_matches(rep, case["report"])


def test_sacrifice_single_class_matches_disagg_goldens(golden, ctx, report_matches):
    model, cluster, store, coll = ctx
    for case in golden["disagg"]:
        scheme = _disagg_scheme(model, cluster, case["mode"])
        plan = map_disagg_scheme(scheme, cluster)
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = DisaggSimulator(plan, store, coll)
        rep = sim.simulate(reqs, keep_records=True, congestion=False,
                           reprefill_occupancy=False,
                           preemption="sacrifice", slo_classes=ONE_TENANT)
        report_matches(rep, case["report"])


# ---------------------------------------------------------------------------
# preemption menu: parsing + labels
# ---------------------------------------------------------------------------

def test_make_preemption_menu():
    assert isinstance(make_preemption(None), SacrificePolicy)
    assert make_preemption(None).label() == "sacrifice/recent"
    assert make_preemption("swap").label() == "swap/recent"
    p = make_preemption("sacrifice/lowest-priority-first")
    assert isinstance(p, SacrificePolicy) and p.victim == "priority"
    assert make_preemption("swap/lifo").victim == "recent"
    inst = SwapPolicy(victim="lowest-priority")
    assert make_preemption(inst) is inst
    with pytest.raises(ValueError, match="mechanism"):
        make_preemption("migrate")
    with pytest.raises(ValueError, match="victim"):
        make_preemption("swap/oldest")
    with pytest.raises(NotImplementedError):
        PreemptionPolicy().evict(None, None, 0.0)


# ---------------------------------------------------------------------------
# victim order: priority eviction shields the high-priority tenant
# ---------------------------------------------------------------------------

def _class_ttft_p95(res):
    by_cls = {}
    for rec in res.records:
        by_cls.setdefault(rec.slo_class.name, []).append(rec.ttft)
    return {name: p95(v) for name, v in by_cls.items()}


def test_lowest_priority_first_wins_ttft_p95():
    """Seeded two-class trace under KV pressure: with
    ``lowest-priority-first`` eviction the high-priority class beats the
    low-priority class on TTFT p95, and beats its own TTFT p95 under the
    class-blind ``recent-first`` order."""
    hi = SLOClass("hi", priority=2)
    lo = SLOClass("lo", priority=0)
    reqs = mixed_trace([("chat", 12.0, hi, 24), ("chat", 12.0, lo, 24)],
                       seed=7)
    cap = max(r.context_len + r.gen_len for r in reqs) + 200

    def run(spec):
        return BatchingModule(cap, BatchingPolicy(),
                              preemption=spec).run(reqs, const_cost())

    prio = run("sacrifice/lowest-priority-first")
    recent = run("sacrifice/recent-first")
    assert prio.preemptions > 0 and recent.preemptions > 0
    assert _class_ttft_p95(prio)["hi"] < _class_ttft_p95(prio)["lo"]
    assert _class_ttft_p95(prio)["hi"] < _class_ttft_p95(recent)["hi"]


# ---------------------------------------------------------------------------
# mechanism: swap preserves progress and is counted separately
# ---------------------------------------------------------------------------

def test_swap_counters_and_progress():
    reqs = mk_requests([(0.0, 60, 40)] * 8)

    sac = BatchingModule(300, BatchingPolicy(),
                         preemption="sacrifice").run(reqs, const_cost())
    swp = BatchingModule(300, BatchingPolicy(), preemption="swap",
                         swap_cost=lambda r, kv: (0.01, 0.5)).run(
        reqs, const_cost())

    # sacrifice never touches the swap counters
    assert sac.preemptions > 0
    assert sac.swap_outs == sac.swap_ins == 0 and sac.kv_swap_s == 0.0
    assert all(r.swaps == 0 and r.swap_s == 0.0 for r in sac.records)

    # every swap-out is paid for, restored, and attributed to its victim
    assert swp.swap_outs > 0
    assert swp.swap_ins == swp.swap_outs == swp.preemptions
    assert swp.kv_swap_s == pytest.approx(0.01 * swp.swap_outs)
    assert sum(r.swaps for r in swp.records) == swp.swap_outs
    assert sum(r.swap_s for r in swp.records) == pytest.approx(swp.kv_swap_s)

    # parked KV means no prompt recompute: the swap run drains faster
    assert swp.total_time < sac.total_time


def test_decode_swap_skips_refetch_and_keeps_first_token():
    """In the disagg decode role, only sacrifice re-fetches shipped
    prompt KV; a swap victim's KV is parked on the host, so no re-fetch
    is charged and its first token is never re-stamped."""
    reqs = mk_requests([(0.0, 200, 5), (0.0, 200, 60)])
    sac = BatchingModule(404, BatchingPolicy(), role="decode").run(
        reqs, const_cost())
    swp = BatchingModule(404, BatchingPolicy(), role="decode",
                         preemption="swap",
                         swap_cost=lambda r, kv: (0.02, 0.0)).run(
        reqs, const_cost())
    assert sac.preemptions > 0 and sac.kv_refetch_s > 0.0
    assert swp.swap_outs > 0 and swp.kv_refetch_s == 0.0
    victim = next(r for r in swp.records if r.swaps > 0)
    assert victim.first_token_time == 0.0  # admitted at t=0, never re-set


# ---------------------------------------------------------------------------
# per-class reporting + goodput
# ---------------------------------------------------------------------------

CHAT_SLO = SLOClass("chat", priority=1, ttft_target_s=0.005,
                    tpot_target_s=3e-4)
SUMM_SLO = SLOClass("summarization", priority=0, ttft_target_s=0.03)


def _two_class_trace():
    return mixed_trace([("chat", 4.0, CHAT_SLO, 48),
                        ("summarization", 1.0, SUMM_SLO, 16)], seed=7)


def test_report_per_class_percentiles_and_goodput(ctx):
    model, cluster, store, coll = ctx
    plan = map_scheme(_colocated_scheme(model, 1), cluster)
    rep = PlanSimulator(plan, store, coll).simulate(
        _two_class_trace(), keep_records=True)

    assert [c.name for c in rep.class_reports] == ["chat", "summarization"]
    assert rep.ttft_p50 <= rep.ttft_p95 <= rep.ttft_p99
    assert rep.tpot_p50 <= rep.tpot_p95 <= rep.tpot_p99
    met = sum(c.slo_met for c in rep.class_reports)
    assert 0 < met <= 64
    assert rep.goodput_rps == pytest.approx(met / rep.e2e_latency)
    assert rep.goodput_rps == pytest.approx(
        sum(c.goodput_rps for c in rep.class_reports))
    assert rep.sacrifices == rep.preemptions - rep.swap_outs

    text = str(rep)
    assert "TTFT p50/p95/p99" in text and "TPOT p50/p95/p99" in text
    assert "[chat p1]" in text and "[summarization p0]" in text
    assert "goodput=" in rep.summary()


def test_classless_goodput_degrades_to_request_throughput(ctx):
    """With no SLO targets anywhere, every finished request counts:
    goodput is plain request throughput."""
    model, cluster, store, coll = ctx
    plan = map_scheme(_colocated_scheme(model, 1), cluster)
    reqs = get_trace("chat", arrival_rate=4.0, seed=11, num_requests=32)
    rep = PlanSimulator(plan, store, coll).simulate(reqs)
    assert rep.goodput_rps == pytest.approx(len(reqs) / rep.e2e_latency)


# ---------------------------------------------------------------------------
# goodput objective: exact, multi-fidelity, and fluid-screen containment
# ---------------------------------------------------------------------------

def test_goodput_search_exact_and_multifid_containment():
    model = ir_from_hf_config(SMALL, name="tiny")
    search = ApexSearch(model, h100_node(4))
    reqs = _two_class_trace()

    res = search.search(reqs, objective="goodput")
    assert res.objective == "goodput"
    goodputs = [r.goodput_rps for r in res.all_reports if r.feasible]
    # the SLO targets bite: plans genuinely differ on goodput, and the
    # winner maximizes it
    assert min(goodputs) < max(goodputs)
    assert res.best.goodput_rps == pytest.approx(max(goodputs))
    assert [c.name for c in res.best.class_reports] == \
        ["chat", "summarization"]

    mf = MultiFidelitySearch(search)
    mres = mf.search(reqs, objective="goodput")
    survivor_labels = [mres.surrogate_reports[i].plan_label
                       for i in mres.survivor_indices]
    # the fluid screen's frontier contains the exact winner, and the
    # confirmed ranking recovers its goodput exactly
    assert res.best.plan_label in survivor_labels
    assert mres.best.goodput_rps == pytest.approx(res.best.goodput_rps)
