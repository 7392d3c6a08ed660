"""Golden-equivalence + congestion properties of the event engine.

``tests/golden/core_golden.json`` freezes the reports the pre-refactor
per-replica loops (``BatchingModule._run_continuous``/``_run_static`` and
the coupled two-pool ``DisaggSimulator``) produced on the three paper
traces (captured at commit ef964aa by tests/golden/capture.py; the legacy
loops are gone, so the JSON cannot be regenerated — that is the point).

  * engine-backed colocated simulation must match the goldens EXACTLY
    (continuous, chunked-prefill, static, batch-capped; model-DP 1 and 2),
    except the TTFT/TPOT means, held to 1e-12 relative because Python
    3.12's float sum() differs from 3.10's (tests/conftest.py);
  * engine-backed homogeneous disagg with the engine couplings disabled
    (independent transfers, delay-only re-fetch) must match the goldens
    EXACTLY — the engine reproduces the independent-transfer model;
  * with the default couplings ON, congestion is monotone: a narrower
    cross-pool link never improves TTFT/TPOT p95, and an effectively
    infinite link reproduces the independent-transfer numbers.
"""

import dataclasses
import json
import os

import pytest

from repro.core import (CollectiveModel, NetworkLevel, ProfileStore,
                        generate_schemes, get_trace, h100_node, h200_node,
                        ir_from_hf_config, map_scheme)
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


def test_colocated_reports_match_legacy_loop_exactly(golden, ctx, report_matches):
    model, cluster, store, coll = ctx
    plans = {dp: map_scheme(_colocated_scheme(model, dp), cluster)
             for dp in (1, 2)}
    assert len(golden["colocated"]) == 24
    for case in golden["colocated"]:
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = PlanSimulator(plans[case["dp"]], store, coll)
        rep = sim.simulate(reqs, policy=POLICIES[case["policy"]],
                           keep_records=True)
        report_matches(rep, case["report"])


def test_disagg_compat_reports_match_legacy_loop_exactly(golden, ctx, report_matches):
    """Engine couplings OFF == the pre-engine independent-transfer +
    delay-only-re-fetch model, bit for bit."""
    model, cluster, store, coll = ctx
    assert len(golden["disagg"]) == 6
    for case in golden["disagg"]:
        scheme = _disagg_scheme(model, cluster, case["mode"])
        plan = map_disagg_scheme(scheme, cluster)
        reqs = get_trace(case["trace"], arrival_rate=case["rate"], seed=11,
                         num_requests=48)
        sim = DisaggSimulator(plan, store, coll)
        rep = sim.simulate(reqs, keep_records=True, congestion=False,
                           reprefill_occupancy=False)
        report_matches(rep, case["report"])


# ---------------------------------------------------------------------------
# SharedLink congestion properties
# ---------------------------------------------------------------------------

def _hetero_plan(model, bw):
    pre_c, dec_c = h100_node(4), h200_node(4)
    for s in generate_disagg_schemes(model, prefill_cluster=pre_c,
                                     decode_cluster=dec_c,
                                     max_plans=100000):
        if (s.prefill.model_dp == 1 and s.decode.model_dp == 1
                and s.prefill.pp_stages == 1 and s.decode.pp_stages == 1):
            link = NetworkLevel("xlink", 8, bw, 2e-6, launch_s=1e-5)
            return map_disagg_scheme(s, prefill_cluster=pre_c,
                                     decode_cluster=dec_c,
                                     cross_level=link), pre_c
    raise RuntimeError("no hetero scheme")


def _simulate_bw(model, reqs, bw, **kw):
    plan, pre_c = _hetero_plan(model, bw)
    sim = DisaggSimulator(plan, ProfileStore(AnalyticBackend(pre_c)),
                          CollectiveModel(pre_c))
    return sim.simulate(reqs, **kw)


def test_shared_link_fifo_monotone_in_service_time():
    """SharedLink invariant: scaling every transfer's wire time up (a
    narrower link) never completes any transfer earlier, and strictly
    queues once requests overlap."""
    from repro.core import SharedLink

    @dataclasses.dataclass
    class Est:
        wire_s: float
        delay_s: float

        @property
        def stream_lead_s(self):
            return max(0.0, self.wire_s - self.delay_s)

    finishes = [0.0, 0.1, 0.11, 0.12, 0.5, 0.51]
    for scale in (1.0, 4.0, 16.0):
        base = [Est(wire_s=0.08, delay_s=0.02)] * len(finishes)
        wide_link, narrow_link = SharedLink(), SharedLink()
        wide = [wide_link.transfer(t, e) for t, e in zip(finishes, base)]
        scaled = [Est(e.wire_s * scale, e.delay_s * scale) for e in base]
        narrow = [narrow_link.transfer(t, e)
                  for t, e in zip(finishes, scaled)]
        for w, n in zip(wide, narrow):
            assert n >= w - 1e-12
        if scale > 1.0:
            assert narrow_link.queued_s > wide_link.queued_s
    # independent mode never queues
    free = SharedLink(congestion=False)
    for t in finishes:
        free.transfer(t, Est(0.08, 0.02))
    assert free.queued_s == 0.0


def test_congestion_monotone_in_link_bandwidth():
    """Summarization (many large simultaneous KV handoffs): narrowing the
    shared wire monotonically queues more transfer time and never
    improves TTFT p95."""
    from repro.core import SharedLink
    model = ir_from_hf_config(SMALL, name="tiny")
    reqs = get_trace("summarization", arrival_rate=8.0, seed=5,
                     num_requests=32)
    bws = [1e13, 2e9, 2e8]          # effectively-infinite -> narrow
    links = [SharedLink() for _ in bws]
    reports = [_simulate_bw(model, reqs, bw, link=link)
               for bw, link in zip(bws, links)]
    for (wide, wl), (narrow, nl) in zip(zip(reports, links),
                                        zip(reports[1:], links[1:])):
        assert narrow.ttft_p95 >= wide.ttft_p95 - 1e-12
        assert nl.queued_s >= wl.queued_s - 1e-12
    # the narrow wire really queues, and the queueing reaches the
    # decode pool: strictly later drain than the uncontended regime
    assert links[-1].queued_s > links[0].queued_s
    assert reports[-1].e2e_latency > reports[0].e2e_latency


def test_infinite_link_reproduces_independent_transfers():
    """A wire fast enough never to queue makes the FIFO invisible: the
    default congestion model returns the independent per-request
    numbers exactly."""
    model = ir_from_hf_config(SMALL, name="tiny")
    reqs = get_trace("summarization", arrival_rate=8.0, seed=5,
                     num_requests=32)
    fifo = _simulate_bw(model, reqs, 1e13, reprefill_occupancy=False)
    indep = _simulate_bw(model, reqs, 1e13, reprefill_occupancy=False,
                         congestion=False)
    for field in ("e2e_latency", "ttft_p95", "tpot_p95", "total_energy",
                  "iterations", "preemptions"):
        assert getattr(fifo, field) == getattr(indep, field), field


def test_congestion_on_by_default_summarization_ttft():
    """Acceptance: with the engine couplings on by default, the
    summarization trace on a hetero pool pair (KV-tight decode pool over
    a narrow cross link) shows TTFT p95 strictly above the PR-2
    independent-transfer model: preempted decode victims re-occupy the
    prefill pool as real re-prefill jobs, delaying other prompts' first
    tokens, and their re-shipped caches queue on the shared wire.

    The model is large enough (16 layers) that prefill takes whole
    milliseconds and arrivals at 60 req/s keep the prefill pool loaded
    while the KV-tight decode pool preempts — so the re-prefills land in
    a busy queue and measurably push the TTFT tail."""
    model = ir_from_hf_config(
        dict(hidden_size=2048, num_hidden_layers=16,
             num_attention_heads=16, num_key_value_heads=8,
             intermediate_size=8192, vocab_size=32000), name="tiny-7b")
    reqs = get_trace("summarization", arrival_rate=60.0, seed=5,
                     num_requests=48)
    pre_c = h100_node(4)
    scheme = next(
        s for s in generate_disagg_schemes(
            model, prefill_cluster=pre_c, decode_cluster=h200_node(4),
            max_plans=100000)
        if s.prefill.model_dp == 1 and s.decode.model_dp == 1
        and s.prefill.pp_stages == 1 and s.decode.pp_stages == 1)
    # decode-pool HBM sized for ~6500 KV tokens: two summarization
    # prompts fit, decode growth overflows -> steady preemption pressure
    per_tok = scheme.decode.kv_bytes_per_token_per_device()
    need = (scheme.decode.weight_bytes_per_device()
            + scheme.decode.state_bytes_per_seq_per_device() * 512
            + 6500 * per_tok)
    kv_tight = dataclasses.replace(h200_node(4).device, name="H200-tight",
                                   hbm_bytes=need / 0.85)
    dec_c = dataclasses.replace(h200_node(4), device=kv_tight,
                                name="h200tight x4")
    link = NetworkLevel("xlink", 8, 2e9, 2e-6, launch_s=1e-5)
    plan = map_disagg_scheme(scheme, prefill_cluster=pre_c,
                             decode_cluster=dec_c, cross_level=link)
    sim = DisaggSimulator(plan, ProfileStore(AnalyticBackend(pre_c)),
                          CollectiveModel(pre_c))
    default = sim.simulate(reqs)
    legacy = sim.simulate(reqs, congestion=False,
                          reprefill_occupancy=False)
    assert default.feasible and legacy.feasible
    assert default.preemptions > 0
    assert default.ttft_p95 > legacy.ttft_p95
