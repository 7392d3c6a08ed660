"""The ``granite4h.decode_batch`` cell: one whole run on the CPU at a tiny
size (through a copy of its configuration file, as ``make_root`` does for
internlm2), its metric readers, and the counts of
``costs/granitemoehybrid.py`` by hand."""

import json
import shutil

import jax.numpy as jnp
import pytest

import cost
import harness
import peaks
import run
from conftest import CHIP, ROOT

CELL = "granite4h.decode_batch"
CFG = json.loads((CHIP / "configs" / "granite-4.0-h-micro.json").read_text())
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
            shared_intermediate_size=192, intermediate_size=192,
            num_hidden_layers=6, layer_types=["mamba", "mamba",
                                              "attention"] * 2,
            vocab_size=512, serving={"max_batch": 4, "max_len": 64})
NEW = ("decode.step_device_ms.granite", "decode_step_roofline.granite",
       "step.mfu.granite", "device.idle_share.granite",
       "sched.occupancy.granite")


@pytest.fixture
def granite_root(tmp_path):
    (tmp_path / "cfg").mkdir()
    cfg = dict(CFG, **TINY)
    (tmp_path / "cfg" / "small.json").write_text(json.dumps(cfg))
    shutil.copy(CHIP / "configs" / "granite-4.0-h-micro.py",
                tmp_path / "cfg" / "small.py")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in bench["configs"]
                if c["name"] == "granite-4.0-h-micro")
    conf["file"] = "cfg/small.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(capsys, root, trace=0):
    rc = run.main(["--workload", CELL, "--seed", "3141592653589",
                   "--seconds", "3", "--trace", str(trace)], root=root,
                  require_tpu=False)
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct(granite_root, cpu_peaks, capsys, monkeypatch,
                           trace):
    # a trace directory of its own, so runs in other processes cannot
    # delete it under this one
    monkeypatch.setattr(harness, "TRACE_DIR", granite_root / "trace")
    rc, res = _run(capsys, granite_root, trace)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:      # the CPU has no device plane: only the host-side one
        assert 0 < res["metrics"]["step.mfu.granite"]["value"] < 100
        assert 0 < res["metrics"]["sched.occupancy.granite"]["value"] <= 100
    else:
        assert set(res["metrics"]) == {"setup_s", "output_tok_s",
                                       "itl_p95_ms"}


def test_altered_token_is_not_correct(granite_root, cpu_peaks, capsys,
                                      monkeypatch):
    """A step that serves each slot's least likely token is not correct
    (the tied head's logits are small: their spread is about 0.01 at this
    width, so the altered token is the one farthest from the best)."""
    from repro.models import transformer as T
    step = T.decode_step

    def broken(params, cfg, tokens, cache, embeds=None):
        logits, new = step(params, cfg, tokens, cache, embeds)
        worst = jnp.argmin(logits, axis=-1)
        rows = jnp.arange(logits.shape[0])
        return logits.at[rows, worst].set(jnp.max(logits) + 1.0), new
    monkeypatch.setattr(T, "decode_step", broken)
    rc, res = _run(capsys, granite_root)
    gap = res["checks"]["widest_logit_gap"]
    assert rc == 0 and res["correct"] is False and gap["value"] > gap["limit"]


def test_new_readers_read_what_their_base_readers_read():
    """Each ``.granite`` reader is its base reader, on a hand-made traced
    context of the full-size configuration."""
    calls = [harness.Call(1.0 + 0.02 * i, "decode", tuple([700] * 32))
             for i in range(10)]
    ctx = harness.Context(
        setup_s=90.0, window=(0.0, 51.0), span=(1.0, 9.0), requests=[],
        calls=calls, trace={"step_device_s": [0.02] * 10,
                            "idle_share": 0.12},
        step_cost=cost.step_cost_for(CFG),
        peaks=peaks.peaks_for("TPU v5 lite"), max_batch=32)
    for name in NEW:
        value = harness.read_metrics([{"name": name, "unit": "x"}], ctx)
        base = name.replace(".granite", ".batch").replace(
            "decode_step_roofline.batch", "decode_step_roofline")
        want = harness.read_metrics([{"name": base, "unit": "x"}], ctx)
        assert value[name]["value"] == want[base]["value"] > 0


# -- costs/granitemoehybrid.py by hand ---------------------------------------

# per Mamba layer: in_proj 2048 x (4096 z + 4352 xBC + 64 dt), out_proj
# 4096 x 2048; conv 4352 x 4 and its bias, A_log, dt_bias, D (64 each),
# the gated norm 4096.  Attention: q, o 2048 x 2048, k, v 2048 x 512.
# MLP 3 x 2048 x 8192 after all 40 layers; tied head 2048 x 100352.
MAMBA = 2048 * 8512 + 4096 * 2048                            # 25821184
MAMBA_VEC = 5 * 4352 + 3 * 64 + 4096                         # 26048
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512                      # 10485760
MLP = 3 * 2048 * 8192                                        # 50331648
HEAD = 2048 * 100352
NORMS = (2 * 40 + 1) * 2048
STATE = 64 * 64 * 128 * 4 + 3 * 4352 * 2      # bytes a slot a Mamba layer
KV = 4 * 2 * 8 * 64 * 2                       # bytes a token, 4 layers


def test_whole_model_is_3191_million_parameters():
    total = 36 * (MAMBA + MAMBA_VEC) + 4 * ATTN + 40 * MLP + HEAD + NORMS
    assert total == 3191396096
    flops, nbytes = cost.step_cost_for(CFG)([1])
    assert nbytes == (total + 2048) * 2 + 36 * 2 * STATE + KV
    assert flops == 2 * (36 * MAMBA + 4 * ATTN + 40 * MLP + HEAD) \
        + 4 * 4 * 32 * 64 + 36 * 4 * 64 * 64 * 128


def test_step_counts_state_per_token_and_kv_at_actual_lengths():
    mod = cost.load_module(CHIP / "costs" / "granitemoehybrid.py")
    ctxs = [100, 700, 2047]
    n, total = 3, sum(ctxs)
    ssm_flops = 36 * n * (2 * MAMBA + 4 * 64 * 64 * 128)
    ssm_bytes = 36 * (MAMBA + MAMBA_VEC) * 2 + 36 * n * 2 * STATE
    assert mod.ssm_decode_cost(CFG, ctxs) == (ssm_flops, ssm_bytes)
    flops, nbytes = mod.step_cost(CFG, ctxs)
    assert flops == ssm_flops + 2 * n * (4 * ATTN + 40 * MLP + HEAD) \
        + 4 * 4 * 32 * 64 * total
    assert nbytes == ssm_bytes + (4 * ATTN + 40 * MLP + HEAD + NORMS
                                  + n * 2048) * 2 + total * KV


def test_least_time_at_full_batch_is_memory_bound():
    """About 11.46 GB a full step (6.38 weights, 4.89 state, 0.18 KV):
    14.0 ms at 819 GB/s against about 1 ms of operations."""
    v5e = peaks.peaks_for("TPU v5 lite")
    flops, nbytes = cost.step_cost_for(CFG)([700] * 32)
    t, bound = peaks.least_time(flops, nbytes, v5e)
    assert bound == "memory" and 13.5e-3 < t < 14.5e-3
    assert flops / v5e.bf16_flops < 1.2e-3
