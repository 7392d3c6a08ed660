"""End-to-end behaviour tests for the APEX system (paper workflow)."""

import pytest

from repro.core import (ApexSearch, BatchingPolicy, compare_three_plans,
                        generate_schemes, get_trace, h100_node,
                        h100_multinode, heuristic_scheme, ir_from_hf_config)


LLAMA70B = dict(hidden_size=8192, num_hidden_layers=80,
                num_attention_heads=64, num_key_value_heads=8,
                intermediate_size=28672, vocab_size=128256)
MIXTRAL = dict(hidden_size=6144, num_hidden_layers=56,
               num_attention_heads=48, num_key_value_heads=8,
               intermediate_size=16384, num_local_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=16384,
               vocab_size=32000)


@pytest.fixture(scope="module")
def llama():
    return ir_from_hf_config(LLAMA70B, name="llama-70b")


@pytest.fixture(scope="module")
def mixtral():
    return ir_from_hf_config(MIXTRAL, name="mixtral-8x22b")


def test_search_beats_or_matches_baseline(llama):
    cluster = h100_node(8)
    reqs = get_trace("chat", arrival_rate=8.0, num_requests=64)
    s = ApexSearch(llama, cluster)
    base = s.evaluate_baseline(reqs)
    res = s.search(reqs, feasible_only=False)
    assert res.best.e2e_latency <= base.e2e_latency * 1.0001
    assert res.num_feasible > 0
    assert res.best.feasible


def test_three_plan_comparison_structure(mixtral):
    cluster = h100_node(8)
    reqs = get_trace("creation", arrival_rate=4.0, num_requests=48)
    out = compare_three_plans(mixtral, cluster, reqs)
    # APEX optimal explores a superset of the feasible space
    assert out["apex_speedup"] >= out["feasible_speedup"] * 0.999
    assert out["baseline"].e2e_latency > 0
    assert out["feasible_optimal"].plan_label
    # the paper's observation: EP shows up for MoE models
    labels = [r.plan_label for r in out["search"].all_reports]
    assert any("ep" in l for l in labels)


def test_report_metrics_sane(llama):
    cluster = h100_node(8)
    reqs = get_trace("summarization", arrival_rate=1.0, num_requests=32)
    s = ApexSearch(llama, cluster)
    rep = s.evaluate_baseline(reqs)
    assert rep.e2e_latency > 0
    assert rep.ttft_mean > 0
    assert rep.tpot_mean > 0
    assert rep.ttft_p95 >= rep.ttft_mean * 0.5
    assert 0 < rep.mfu <= 1
    assert 0 < rep.mbu <= 1
    assert rep.total_energy > 0
    assert rep.throughput_tok_s > 0


def test_slo_constrained_search(llama):
    cluster = h100_node(8)
    reqs = get_trace("chat", arrival_rate=4.0, num_requests=48)
    s = ApexSearch(llama, cluster)
    res = s.search(reqs, objective="latency", slo_tpot_s=1.0)
    assert res.best.tpot_p95 <= 1.0


def test_energy_objective_differs(llama):
    """Energy-optimal may differ from latency-optimal (paper §4.2.4)."""
    cluster = h100_node(8)
    reqs = get_trace("summarization", arrival_rate=1.0, num_requests=32)
    s = ApexSearch(llama, cluster)
    lat = s.search(reqs, objective="latency")
    en = s.search(reqs, objective="energy")
    assert en.best.total_energy <= lat.best.total_energy * 1.0001


def test_multinode_baseline_uses_pp(llama):
    cluster = h100_multinode(2)
    scheme = heuristic_scheme(llama, 16, cluster)
    assert scheme.pp_stages == 2           # TP in node, PP across (paper)
    assert scheme.stage_devices == 8


def test_batching_policy_max_batch(llama):
    cluster = h100_node(8)
    reqs = get_trace("creation", arrival_rate=4.0, num_requests=32)
    s = ApexSearch(llama, cluster)
    uncapped = s.evaluate_baseline(reqs)
    capped = s.evaluate_baseline(
        reqs, policy=BatchingPolicy(max_batch_size=2))
    assert capped.peak_batch <= 2
    assert capped.e2e_latency >= uncapped.e2e_latency * 0.999


# ---------------------------------------------------------------------------
# v5e cluster presets and the device-kind table
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,chips", [("tpu-v5e-1", 1), ("tpu-v5e-4", 4)])
def test_tpu_v5e_host_presets(name, chips):
    from repro.core import get_cluster
    from repro.core.cluster import TPU_V5E
    clu = get_cluster(name)
    assert clu.num_devices == chips and clu.device is TPU_V5E
    assert clu.level_for_group(chips).group_size >= chips


def test_device_kind_table_has_no_default():
    from repro.core.cluster import TPU_V5E, cluster_for_device_kind
    assert cluster_for_device_kind("TPU v5 lite").device is TPU_V5E
    assert cluster_for_device_kind("cpu").name == "cpu-local"
    with pytest.raises(KeyError, match="TPU v6 lite"):
        cluster_for_device_kind("TPU v6 lite")


def test_measured_backend_times_in_the_ops_dtype():
    """The profiler pairs its timings with this device's DeviceSpec and
    runs each op in its compute dtype; a dtype it cannot run raises."""
    import jax
    from repro.core import MeasuredBackend
    be = MeasuredBackend(repeats=1)
    assert be.cluster.device.name == {
        "cpu": "cpu-local", "TPU v5 lite": "TPU-v5e"}[
            jax.devices()[0].device_kind]
    fn, (a, b) = be._build("gemm", (32, 16, "bf16"), 8.0)
    assert a.dtype == b.dtype == jax.numpy.bfloat16
    t, e = be.measure("attn_decode", (2, 16, "bf16"), 64.0)
    assert t > 0 and e > 0
    with pytest.raises(KeyError, match="fp8"):
        be.measure("gemm", (32, 16, "fp8"), 8.0)
