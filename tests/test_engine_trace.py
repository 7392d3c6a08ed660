"""The serving engine's spans (``repro.serving.telemetry``): recording them
changes nothing served, they form a well-made tree whose counts agree with
the engine's report, and they sit on the profiler's clock."""

import pathlib

import jax
import pytest

from repro import configs as C
from repro.models import transformer as T
from repro.serving import telemetry
from repro.serving.engine import ServingEngine
from repro.serving.telemetry import EngineTrace, Span


@pytest.fixture(scope="module")
def small():
    cfg = C.get_reduced("qwen2_0_5b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(n, prompt=5, gen=4):
    return [dict(rid=r, arrival=0.0,
                 prompt=[(7 * r + 3 * t) % 50 + 1 for t in range(prompt + r)],
                 gen_len=gen) for r in range(n)]


def _served(report):
    return {r.rid: r.tokens for r in report.results}


@pytest.fixture(scope="module")
def traced(small):
    """Five requests due at once on three slots: the schedule depends on no
    timing, and all but the first wait for another's prefill or decode."""
    cfg, params = small
    trace = EngineTrace()
    eng = ServingEngine(cfg, params, max_batch=3, max_len=64, trace=trace)
    reqs = _reqs(5)
    return reqs, eng.run(reqs), trace.spans


def test_recorder_changes_no_token(small, traced):
    cfg, params = small
    reqs, report, spans = traced
    plain = ServingEngine(cfg, params, max_batch=3, max_len=64)
    assert plain.trace is None
    assert _served(plain.run(reqs)) == _served(report)
    assert spans


def test_span_tree_is_well_formed(traced):
    _, _, spans = traced
    parent_of = {"engine.prefill.sync": "engine.prefill",
                 "engine.decode.call": "engine.decode",
                 "engine.decode.sync": "engine.decode",
                 "engine.evict": "engine.decode"}
    for k, sp in enumerate(spans):
        assert sp.name in telemetry.SPAN_NAMES
        assert sp.start <= sp.end
        if k:
            assert spans[k - 1].start <= sp.start       # in opening order
        if sp.parent is None:
            assert sp.name in ("engine.prefill", "engine.decode")
            continue
        assert 0 <= sp.parent < k
        up = spans[sp.parent]
        assert up.name == parent_of[sp.name]
        assert up.start <= sp.start and sp.end <= up.end
        if up.rid is not None:
            assert sp.rid == up.rid                     # a request's spans
    assert {s.name for s in spans} >= set(telemetry.SPAN_NAMES) - {
        "engine.evict"}


def test_one_prefill_per_admitted_request(traced):
    reqs, report, spans = traced
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert sorted(s.rid for s in prefills) == [r["rid"] for r in reqs]
    lengths = {r["rid"]: len(r["prompt"]) for r in reqs}
    for s in prefills:
        assert s.attrs["tokens"] == lengths[s.rid]
        assert s.attrs["waited_s"] >= 0
        # the length reset, one fix-up per replayed token, the first token
        syncs = [c for c in spans if c.parent == spans.index(s)]
        assert len(syncs) == lengths[s.rid] + 2
    assert any(s.attrs["waited_s"] > 0 for s in prefills)


def test_decode_spans_count_iterations_and_kv_in_use(traced):
    _, report, spans = traced
    decodes = [s for s in spans if s.name == "engine.decode"]
    assert len(decodes) == report.iterations
    for s in decodes:
        assert s.attrs["kv_reserved"] == 3 * 64
        assert 0 < s.attrs["kv_tokens"] <= s.attrs["kv_reserved"]
        assert 1 <= s.attrs["active"] <= 3
        assert s.attrs["state_reserved"] == 0          # no SSM layer
    line = telemetry.summary(spans)
    assert line.startswith(f"{report.iterations} decode iterations, ")


def test_ssm_state_reserved_and_no_new_syncs():
    """With SSM layers (reduced Granite: 4 Mamba-2 layers), ``engine.decode``
    carries the bytes of recurrent state the cache holds for all slots, and
    a replay makes as many host syncs as without: the uploads of the slots
    the steps advance ride in its first and last."""
    cfg = C.get_reduced("granite_4_0_h_micro")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    trace = EngineTrace()
    eng = ServingEngine(cfg, params, max_batch=3, max_len=64, trace=trace)
    reqs = _reqs(5)
    report = eng.run(reqs)
    # a slot a Mamba layer: float32 state (8 heads x 16 x 16) and bf16
    # conv windows (3 positions of 128 + 2 x 16 channels)
    per_slot = 4 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 2)
    decodes = [s for s in trace.spans if s.name == "engine.decode"]
    assert len(decodes) == report.iterations > 0
    assert {s.attrs["state_reserved"] for s in decodes} == {3 * per_slot}
    lengths = {r["rid"]: len(r["prompt"]) for r in reqs}
    for k, s in enumerate(trace.spans):
        if s.name == "engine.prefill":
            syncs = [c for c in trace.spans if c.parent == k]
            assert len(syncs) == lengths[s.rid] + 2


def test_evict_spans_count_preemptions(small):
    """Two 8-token prompts fill an 18-token budget; the first decode step
    overshoots it and evicts the later request."""
    cfg, params = small
    trace = EngineTrace()
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64,
                        kv_token_budget=18, trace=trace)
    reqs = _reqs(2, prompt=8, gen=6)
    for r in reqs:
        r["prompt"] = r["prompt"][:8]
    report = eng.run(reqs)
    evicts = [s for s in trace.spans if s.name == "engine.evict"]
    assert report.preemptions >= 1
    assert len(evicts) == report.preemptions
    assert f"{report.preemptions} preemptions" in telemetry.summary(
        trace.spans)
    assert len(report.results) == 2


def _span(name, start, end, **attrs):
    return Span(name, start, end, None, None, attrs)


def test_summary_on_hand_made_spans():
    """The host gap is taken between decode calls with no prefill between;
    prefill time and its syncs are weighed by tokens replayed; KV share is
    a mean over decode iterations."""
    spans = [
        _span("engine.decode", 0.0, 1.2, kv_tokens=10, kv_reserved=100,
              active=2),
        _span("engine.decode.call", 0.1, 1.0),
        _span("engine.decode.sync", 1.0, 1.1),
        _span("engine.decode", 1.2, 2.3, kv_tokens=30, kv_reserved=100,
              active=3),
        _span("engine.decode.call", 1.3, 2.0),      # gap 0.3 after 1.0
        _span("engine.decode.sync", 2.0, 2.3),
        _span("engine.prefill", 2.4, 3.4, tokens=4, waited_s=0.5),
        _span("engine.prefill.sync", 2.5, 2.9),
        _span("engine.decode", 3.5, 4.4, kv_tokens=20, kv_reserved=100,
              active=4),
        _span("engine.decode.call", 3.6, 4.1),      # prefill between: none
        _span("engine.decode.sync", 4.1, 4.3),
        _span("engine.decode.call", 4.6, 5.0),      # gap 0.5 after 4.1
        _span("engine.prefill", 5.2, 5.8, tokens=2, waited_s=0.0),
        _span("engine.prefill.sync", 5.3, 5.5),
        _span("engine.evict", 5.9, 6.0),
    ]
    assert telemetry.summary(spans) == (
        "3 decode iterations, 3.0 slots active: host 400.00 ms per step "
        "against 600.00 ms waiting on the device, length fix-up 200.00 ms, "
        "KV in use 20.0% of reserved; prefill 266.67 ms per token, of it "
        "host syncs 100.00 ms, admission wait median 250.00 ms; "
        "1 preemptions (host clock)")
    assert telemetry.summary([]) == (
        "0 decode iterations, 0.0 slots active: host - ms per step against "
        "- ms waiting on the device, length fix-up - ms, KV in use - of "
        "reserved; prefill - ms per token, of it host syncs - ms, admission "
        "wait median - ms; 0 preemptions (host clock)")


def _host_events(profile_dir):
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    events = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in telemetry.SPAN_NAMES:
                        events.setdefault(e.name, []).append(e.start_ns * 1e-9)
    return {n: sorted(ts) for n, ts in events.items()}


@pytest.mark.parametrize("record", [True, False], ids=["on", "off"])
def test_spans_on_the_profilers_clock(small, tmp_path, record):
    """On: every recorded span is a host-plane event of the same name whose
    start agrees within 1 ms, after one offset taken from the first span.
    Off: no engine span reaches the profiler."""
    cfg, params = small
    trace = EngineTrace() if record else None
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64, trace=trace)
    reqs = _reqs(3)
    eng.run(reqs[:1])                                   # compile outside
    if record:
        trace.spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        eng.run(reqs)
    events = _host_events(tmp_path)
    if not record:
        assert events == {}
        return
    spans = trace.spans
    assert {n: len(ts) for n, ts in events.items()} == {
        n: sum(s.name == n for s in spans) for n in events}
    offset = events[spans[0].name][0] - spans[0].start
    seen = {}
    for sp in spans:
        k = seen[sp.name] = seen.get(sp.name, -1) + 1
        assert abs(events[sp.name][k] - offset - sp.start) < 1e-3, sp
