"""The ``dsv2lite.decode_batch`` cell: one whole run on the CPU at a tiny
size (through a copy of its configuration file, as ``make_root`` does for
internlm2), its metric readers, and the counts of ``costs/deepseek_v2.py``
by hand."""

import json
import shutil

import jax.numpy as jnp
import pytest

import cost
import harness
import peaks
import run
from conftest import CHIP, ROOT

CELL = "dsv2lite.decode_batch"
CFG = json.loads((CHIP / "configs" / "deepseek-v2-lite-stage.json").read_text())
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=96, moe_intermediate_size=48,
            n_routed_experts=8, num_experts_per_tok=2, num_hidden_layers=4,
            vocab_size=512, serving={"max_batch": 4, "max_len": 64})
NEW = ("decode.step_device_ms.dsv2", "decode_step_roofline.dsv2",
       "step.mfu.dsv2", "device.idle_share.dsv2", "sched.occupancy.dsv2")


@pytest.fixture
def dsv2_root(tmp_path):
    (tmp_path / "cfg").mkdir()
    cfg = dict(CFG, **TINY)
    (tmp_path / "cfg" / "small.json").write_text(json.dumps(cfg))
    shutil.copy(CHIP / "configs" / "deepseek-v2-lite-stage.py",
                tmp_path / "cfg" / "small.py")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in bench["configs"]
                if c["name"] == "deepseek-v2-lite-stage")
    conf["file"] = "cfg/small.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(capsys, root, trace=0):
    rc = run.main(["--workload", CELL, "--seed", "1234567890123",
                   "--seconds", "3", "--trace", str(trace)], root=root,
                  require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct(dsv2_root, cpu_peaks, capsys, monkeypatch,
                           trace):
    # a trace directory of its own, so runs in other processes cannot
    # delete it under this one
    monkeypatch.setattr(harness, "TRACE_DIR", dsv2_root / "trace")
    rc, res = _run(capsys, dsv2_root, trace)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:      # the CPU has no device plane: only the host-side one
        assert 0 < res["metrics"]["step.mfu.dsv2"]["value"] < 100
        assert 0 < res["metrics"]["sched.occupancy.dsv2"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"setup_s", "output_tok_s",
                                       "itl_p95_ms"}


def test_altered_token_is_not_correct(dsv2_root, cpu_peaks, capsys,
                                      monkeypatch):
    from repro.models import transformer as T
    step = T.decode_step

    def broken(params, cfg, tokens, cache, embeds=None):
        logits, new = step(params, cfg, tokens, cache, embeds)
        return logits.at[:, 7].set(jnp.max(logits) + 1.0), new
    monkeypatch.setattr(T, "decode_step", broken)
    rc, res = _run(capsys, dsv2_root)
    gap = res["checks"]["widest_logit_gap"]
    assert rc == 0 and res["correct"] is False and gap["value"] > gap["limit"]


def test_new_readers_read_what_their_base_readers_read():
    """Each ``.dsv2`` reader is its base reader, on a hand-made traced
    context of the full-size configuration."""
    calls = [harness.Call(1.0 + 0.03 * i, "decode", tuple([700] * 32))
             for i in range(10)]
    ctx = harness.Context(
        setup_s=60.0, window=(0.0, 51.0), span=(1.0, 9.0), requests=[],
        calls=calls, trace={"step_device_s": [0.025] * 10,
                            "idle_share": 0.12},
        step_cost=cost.step_cost_for(CFG),
        peaks=peaks.peaks_for("TPU v5 lite"), max_batch=32)
    for name in NEW:
        value = harness.read_metrics([{"name": name, "unit": "x"}], ctx)
        base = name.replace(".dsv2", ".batch").replace(
            "decode_step_roofline.batch", "decode_step_roofline")
        want = harness.read_metrics([{"name": base, "unit": "x"}], ctx)
        assert value[name]["value"] == want[base]["value"] > 0


# -- costs/deepseek_v2.py by hand ------------------------------------------

# per layer: W_q 2048x3072, W_kva 2048x576, kv norm 512, W_kvb 512x4096,
# W_o 2048x2048; dense FFN 3 x 2048x10944; router 2048x64; 64 experts of
# 3 x 2048x1408; shared 3 x 2048x2816; head 2048x102400; 7 layers, 1 dense.
ATTN = 6291456 + 1179648 + 2097152 + 4194304                 # 13762560
DENSE = 3 * 2048 * 10944                                     # 67239936
EXPERT = 3 * 2048 * 1408                                     # 8650752
SHARED = 3 * 2048 * 2816                                     # 17301504
HEAD = 2048 * 102400
NORMS = 7 * 512 + (2 * 7 + 1) * 2048
LATENT = 7 * 576 * 2                                         # bytes a token


def test_whole_model_is_4009_million_parameters():
    per_moe = 2048 * 64 + 64 * EXPERT + SHARED
    total = 2 * HEAD + 7 * ATTN + DENSE + 6 * per_moe + NORMS
    assert total == 4009526784
    flops, nbytes = cost.step_cost_for(CFG)([1])
    # one token: one expert row per pick, 6 of 64 experts hit
    hit = 64 * (1 - (1 - 6 / 64) ** 1)
    assert hit == 6.0
    assert nbytes == (total - HEAD - 58 * 6 * EXPERT + 2048) * 2 + LATENT


@pytest.mark.parametrize("n, hit", [(1, 6.0), (32, 61.26), (1000, 64.0)])
def test_expected_experts_hit(n, hit):
    mod = cost.load_module(CHIP / "costs" / "deepseek_v2.py")
    assert mod.experts_hit(CFG, n) == pytest.approx(hit, abs=5e-3)


def test_decode_step_absorbed_flops_and_latent_bytes():
    mod = cost.load_module(CHIP / "costs" / "deepseek_v2.py")
    ctxs = [100, 700, 2047]
    n, total = 3, sum(ctxs)
    # absorbed MLA a token a layer: W_q, W_kva, q_nope through W_uk
    # (16 x 128x512), latent readout through W_uv (16 x 512x128), W_o;
    # scores on 512 + 64 and readout on 512 a head at each context entry
    proj = 6291456 + 1179648 + 16 * 128 * 512 + 16 * 512 * 128 + 4194304
    mla_flops = 7 * (2 * proj * n + 2 * 16 * (512 + 64 + 512) * total)
    mla_bytes = (7 * (ATTN + 512) + LATENT // 2 * total) * 2
    assert mod.mla_decode_cost(CFG, ctxs) == (mla_flops, mla_bytes)
    hit = mod.experts_hit(CFG, n)
    assert mod.moe_experts_cost(CFG, ctxs) == (
        2 * 6 * 6 * EXPERT * n, 6 * hit * EXPERT * 2)
    flops, nbytes = mod.step_cost(CFG, ctxs)
    assert flops == mla_flops + 2 * n * (DENSE + HEAD + 6 * (
        2048 * 64 + 6 * EXPERT + SHARED))
    assert nbytes == pytest.approx(
        (7 * ATTN + 7 * 512 + DENSE + HEAD + (2 * 7 + 1) * 2048 + n * 2048
         + 6 * (2048 * 64 + hit * EXPERT + SHARED)) * 2 + total * LATENT)


def test_least_time_at_full_batch_is_memory_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.least_time(*cost.step_cost_for(CFG)([700] * 32), v5e)
    assert bound == "memory" and 9e-3 < t < 11e-3
