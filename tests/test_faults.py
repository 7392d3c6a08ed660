"""Fault injection, graceful degradation, and resilience-aware search.

Covers the four contracts the fault subsystem makes:

  * DETERMINISM — a seeded ``FaultSchedule`` replays bit-identically,
    and an EMPTY schedule is bit-identical to the frozen pre-fault
    goldens (tests/golden/core_golden.json), colocated AND disagg: the
    fault machinery is provably inert when no fault fires.
  * DEGRADATION — killing a replica mid-trace re-queues its in-flight
    work to survivors (KV lost, recompute path), hurts the latency tail,
    and is accounted in the ``ResilienceReport``.
  * ISOLATION — step costs priced under a degraded cluster state live in
    their own ``SharedCostStore`` bucket; a degraded-link run can never
    reuse (or pollute) a healthy-state cost entry.
  * SEARCH — ``objective="degraded_goodput"`` re-simulates candidates
    under the ensemble, identically serial or forked; the multi-fidelity
    ladder screens fault-free and pays for faults only at final confirm;
    bad inputs raise ``ValueError`` early; a crash inside a candidate
    evaluation names the candidate (``PlanEvaluationError``).
"""

import dataclasses
import json
import os

import pytest

from repro.core import (ApexSearch, CollectiveModel, FaultSchedule,
                        LinkDegradation, MultiFidelitySearch,
                        PlanEvaluationError, ProfileStore, ReplicaFault,
                        SharedCostStore, Straggler, fault_ensemble,
                        fork_map, generate_schemes, get_trace, h100_node,
                        ir_from_hf_config, map_scheme, normalize_faults)
from repro.core.batching import BatchingPolicy
from repro.core.profiles import AnalyticBackend
from repro.core.simulator import PlanSimulator
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


def _colocated_plan(model, cluster, dp):
    scheme = next(s for s in generate_schemes(model, 8, quant="fp16")
                  if s.model_dp == dp and s.pp_stages == 1
                  and s.is_feasible_for_current_systems())
    return map_scheme(scheme, cluster)


def _disagg_plan(model, cluster, mode="layerwise"):
    scheme = next(
        s for s in generate_disagg_schemes(model, cluster,
                                           max_plans=100000,
                                           transfer_mode=mode)
        if s.prefill_devices == 4 and s.decode_devices == 4
        and s.prefill.model_dp == 1 and s.decode.model_dp == 1
        and s.prefill.pp_stages == 1 and s.decode.pp_stages == 1)
    return map_disagg_scheme(scheme, cluster)


# ---------------------------------------------------------------------------
# determinism: empty schedule == frozen goldens; same seed == same bits
# ---------------------------------------------------------------------------

def test_empty_schedule_matches_colocated_goldens_exactly(golden, ctx, report_matches):
    """faults=FaultSchedule() must be invisible: every frozen colocated
    golden case reproduces bit for bit with the (empty) schedule
    threaded through the whole fault plumbing."""
    model, cluster, store, coll = ctx
    plans = {dp: _colocated_plan(model, cluster, dp) for dp in (1, 2)}
    empty = FaultSchedule()
    assert empty.empty and empty.cost_key() == ()
    for case in golden["colocated"]:
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = PlanSimulator(plans[case["dp"]], store, coll)
        rep = sim.simulate(reqs, policy=POLICIES[case["policy"]],
                           keep_records=True, faults=empty)
        report_matches(rep, case["report"])
        assert rep.resilience is None


def test_empty_schedule_matches_disagg_goldens_exactly(golden, ctx, report_matches):
    model, cluster, store, coll = ctx
    for case in golden["disagg"]:
        plan = _disagg_plan(model, cluster, case["mode"])
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = DisaggSimulator(plan, store, coll)
        rep = sim.simulate(reqs, keep_records=True, congestion=False,
                           reprefill_occupancy=False,
                           faults=FaultSchedule())
        report_matches(rep, case["report"])
        assert rep.resilience is None


def test_seeded_schedule_is_deterministic(ctx):
    """Same seed -> same schedule -> bit-identical faulted reports,
    including every ``ResilienceReport`` field."""
    model, cluster, store, coll = ctx
    plan = _colocated_plan(model, cluster, 2)
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=32)
    assert FaultSchedule.sample(7, 30.0, 2, replica_mtbf_s=10.0) == \
        FaultSchedule.sample(7, 30.0, 2, replica_mtbf_s=10.0)
    sched = FaultSchedule.sample(7, 30.0, 2, replica_mtbf_s=10.0,
                                 straggler_mtbf_s=20.0)
    assert not sched.empty
    reps = [PlanSimulator(plan, store, coll).simulate(reqs, faults=sched)
            for _ in range(2)]
    assert reps[0].resilience is not None
    assert dataclasses.asdict(reps[0]) == dataclasses.asdict(reps[1])


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------

def test_replica_failure_requeues_to_survivors(ctx):
    """Kill replica 0 of a DP-2 plan mid-trace: its in-flight requests
    lose their KV and re-queue to the survivor (preemption path), the
    TTFT tail degrades, nothing is dropped, and the report says so."""
    model, cluster, store, coll = ctx
    plan = _colocated_plan(model, cluster, 2)
    # arrivals every ~15ms against ~35ms of service keep both replicas
    # busy through the burst, so the kill catches in-flight work
    reqs = get_trace("summarization", arrival_rate=64.0, seed=3,
                     num_requests=32)
    sim = PlanSimulator(plan, store, coll)
    nominal = sim.simulate(reqs)
    kill = FaultSchedule(replica_faults=(
        ReplicaFault(replica=0, start=0.15, repair=1.0, pool="serve"),))
    faulted = sim.simulate(reqs, faults=kill)
    res = faulted.resilience
    assert res is not None
    assert res.requests_requeued > 0
    assert faulted.preemptions >= nominal.preemptions + res.requests_requeued
    assert res.requests_dropped == 0
    assert res.requests_finished == len(reqs)
    assert res.availability < 1.0
    assert res.degraded_seconds > 0.0
    assert faulted.ttft_p95 >= nominal.ttft_p95
    assert res.goodput_rps <= nominal.goodput_rps + 1e-12


def test_unrepaired_failure_of_sole_replica_drops_requests(ctx):
    """A dp=1 plan losing its only replica forever cannot finish the
    queued work — the report must say DROPPED, not hang or lie."""
    model, cluster, store, coll = ctx
    plan = _colocated_plan(model, cluster, 1)
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=32)
    sim = PlanSimulator(plan, store, coll)
    rep = sim.simulate(reqs, faults=FaultSchedule(replica_faults=(
        ReplicaFault(replica=0, start=1.0),)))
    assert rep.resilience.requests_dropped > 0
    assert rep.resilience.requests_finished < len(reqs)


def test_straggler_slows_without_polluting_costs(ctx):
    """A straggler window raises e2e/energy; the step-cost scale is
    applied after the cache lookup, so a subsequent fault-free run on
    the SAME simulator still matches its own baseline bit for bit."""
    model, cluster, store, coll = ctx
    plan = _colocated_plan(model, cluster, 1)
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=24)
    sim = PlanSimulator(plan, store, coll)
    before = sim.simulate(reqs)
    slow = sim.simulate(reqs, faults=FaultSchedule(stragglers=(
        Straggler(replica=0, start=0.0, end=1e9, slowdown=3.0),)))
    after = sim.simulate(reqs)
    assert slow.e2e_latency > before.e2e_latency
    assert slow.total_energy > before.total_energy
    assert dataclasses.asdict(before) == dataclasses.asdict(after)


def test_staged_disagg_mode_rejects_faults(ctx):
    """reprefill_occupancy=False runs the pools as two staged engines —
    there is no coupled timeline to inject into, so a non-empty
    schedule must be rejected loudly rather than half-applied."""
    model, cluster, store, coll = ctx
    plan = _disagg_plan(model, cluster)
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=16)
    sim = DisaggSimulator(plan, store, coll)
    with pytest.raises(ValueError, match="reprefill_occupancy"):
        sim.simulate(reqs, reprefill_occupancy=False,
                     faults=FaultSchedule(replica_faults=(
                         ReplicaFault(replica=0, start=1.0, repair=2.0,
                                      pool="decode"),)))


# ---------------------------------------------------------------------------
# cost-store isolation (adversarial)
# ---------------------------------------------------------------------------

def test_degraded_state_never_reuses_healthy_cost_entries(ctx):
    """Adversarial: a link-degraded disagg run and a straggler-degraded
    colocated run must open NEW SharedCostStore buckets (fingerprint
    carries the fault key), leaving every healthy bucket untouched —
    even though the degraded runs price the very same workloads."""
    model, cluster, _, _ = ctx
    store = ProfileStore(AnalyticBackend(cluster))
    coll = CollectiveModel(cluster)
    cost_store = SharedCostStore()
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=16)

    plan = _disagg_plan(model, cluster)
    sim = DisaggSimulator(plan, store, coll, cost_store=cost_store)
    sim.simulate(reqs)
    healthy_keys = set(cost_store.tables)
    healthy_sizes = {k: len(t) for k, t in cost_store.tables.items()}

    def has_fault_marker(key):
        return any(isinstance(el, tuple) and el[:1] == ("faults",)
                   for el in key)

    assert healthy_keys and not any(map(has_fault_marker, healthy_keys))

    degr = FaultSchedule(link_faults=(
        LinkDegradation(start=0.0, end=1e9, factor=8.0),))
    sim.simulate(reqs, faults=degr)
    new_keys = set(cost_store.tables) - healthy_keys
    assert new_keys, "degraded run must not share a healthy bucket"
    assert all(has_fault_marker(key) for key in new_keys)
    assert all(("faults",) + degr.cost_key() in key for key in new_keys)
    # healthy buckets neither grew nor shrank: zero cross-pollution
    assert {k: len(cost_store.tables[k]) for k in healthy_keys} == \
        healthy_sizes

    cplan = _colocated_plan(model, cluster, 1)
    csim = PlanSimulator(cplan, store, coll, cost_store=cost_store)
    csim.simulate(reqs)
    base_keys = set(cost_store.tables)
    csim.simulate(reqs, faults=FaultSchedule(stragglers=(
        Straggler(replica=0, start=0.0, end=1e9, slowdown=2.0),)))
    assert set(cost_store.tables) - base_keys, \
        "straggler run must open its own bucket"


def test_distinct_schedules_get_distinct_buckets():
    a = FaultSchedule(link_faults=(LinkDegradation(0.0, 5.0, 4.0),))
    b = FaultSchedule(link_faults=(LinkDegradation(0.0, 5.0, 8.0),))
    assert a.cost_key() != b.cost_key()
    assert FaultSchedule().cost_key() == ()


# ---------------------------------------------------------------------------
# resilience-aware search
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def search_ctx():
    model = ir_from_hf_config(SMALL, name="tiny")
    reqs = get_trace("summarization", arrival_rate=4.0, seed=3,
                     num_requests=24)
    ens = fault_ensemble(11, 2, horizon_s=10.0, n_replicas=2,
                         pool="serve", replica_mtbf_s=6.0,
                         replica_mttr_s=4.0)
    return model, reqs, ens


def test_degraded_goodput_search_serial_equals_forked(search_ctx):
    model, reqs, ens = search_ctx
    r1 = ApexSearch(model, h100_node(8)).search(
        reqs, objective="degraded_goodput", faults=ens, max_model_dp=2)
    r2 = ApexSearch(model, h100_node(8)).search(
        reqs, objective="degraded_goodput", faults=ens, max_model_dp=2,
        jobs=2)
    assert dataclasses.asdict(r1.best) == dataclasses.asdict(r2.best)
    assert [dataclasses.asdict(r) for r in r1.all_reports] == \
        [dataclasses.asdict(r) for r in r2.all_reports]
    assert all(r.resilience is not None and
               r.resilience.ensemble_size == len(ens)
               for r in r1.all_reports if r.feasible)


def test_multifid_faults_confirm_only(search_ctx):
    """Screening and rungs stay fault-free; only confirmed finalists
    carry resilience — and the winner agrees with the exact search."""
    model, reqs, ens = search_ctx
    exact = ApexSearch(model, h100_node(8)).search(
        reqs, objective="degraded_goodput", faults=ens, max_model_dp=2)
    mres = MultiFidelitySearch(ApexSearch(model, h100_node(8)),
                               frontier_k=4).search(
        reqs, objective="degraded_goodput", faults=ens, max_model_dp=2)
    assert all(r.resilience is None for r in mres.surrogate_reports)
    assert all(r.resilience is not None
               for r in mres.result.all_reports if r.feasible)
    assert mres.best.plan_label == exact.best.plan_label


# ---------------------------------------------------------------------------
# input validation
# ---------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError):
        ReplicaFault(replica=-1, start=0.0, repair=1.0)
    with pytest.raises(ValueError):
        ReplicaFault(replica=0, start=5.0, repair=5.0)
    with pytest.raises(ValueError):
        LinkDegradation(start=0.0, end=1.0, factor=0.5)
    with pytest.raises(ValueError):
        Straggler(replica=0, start=0.0, end=1.0, slowdown=0.9)
    with pytest.raises(ValueError):
        FaultSchedule(throttle=0.0)
    with pytest.raises(ValueError):
        fault_ensemble(1, 0, horizon_s=10.0, n_replicas=2)
    with pytest.raises(TypeError):
        normalize_faults(["not a schedule"])
    assert normalize_faults(None) == ()
    assert normalize_faults(FaultSchedule()) == ()


def test_search_validation(search_ctx):
    model, reqs, ens = search_ctx
    s = ApexSearch(model, h100_node(8))
    with pytest.raises(ValueError, match="unknown objective"):
        s.search(reqs, objective="nope")
    with pytest.raises(ValueError, match="jobs"):
        s.search(reqs, jobs=-1)
    with pytest.raises(ValueError, match="degraded_goodput"):
        s.search(reqs, objective="degraded_goodput")
    with pytest.raises(ValueError, match="frontier_k"):
        MultiFidelitySearch(s, frontier_k=0)
    with pytest.raises(ValueError, match="strictly increasing"):
        MultiFidelitySearch(s, rungs=(0.5, 0.25))
    with pytest.raises(ValueError, match="rung fractions"):
        MultiFidelitySearch(s, rungs=(0.25, 1.5))
    mf = MultiFidelitySearch(s)
    with pytest.raises(ValueError, match="degraded_goodput"):
        mf.search(reqs, objective="degraded_goodput")
    with pytest.raises(ValueError, match="jobs"):
        mf.search(reqs, jobs=-2)


# ---------------------------------------------------------------------------
# fork_map failure identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jobs", [1, 2])
def test_fork_map_names_the_failing_candidate(jobs):
    def boom(i):
        if i == 2:
            raise RuntimeError("kaput")
        return i

    with pytest.raises(PlanEvaluationError) as exc:
        fork_map(boom, 5, jobs, label=lambda i: f"plan-{i}")
    assert exc.value.index == 2
    assert exc.value.label == "plan-2"
    assert "kaput" in str(exc.value)
    # healthy runs are unaffected
    assert fork_map(lambda i: i * i, 4, jobs) == [0, 1, 4, 9]
