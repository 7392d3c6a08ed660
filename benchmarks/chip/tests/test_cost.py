import json

import pytest

import cost
import peaks

CFG = json.loads((cost.HERE / "configs" / "internlm2-1.8b.json").read_text())

# internlm2-1.8b by hand: per layer q 2048x2048, k and v 2048x1024 each,
# o 2048x2048, SwiGLU 3 x 2048x8192; head 2048x92544; 24 layers.
LAYER_MM = 4194304 + 2 * 2097152 + 4194304 + 3 * 16777216     # 62914560
MM = 24 * LAYER_MM + 2048 * 92544                             # 1699479552
NORMS = (2 * 24 + 1) * 2048
KV_TOKEN = 24 * 2 * 8 * 128 * 2                               # 98304 bytes


def test_weights_read_match_the_served_bytes():
    # all weights but the embedding table, plus one embedding row
    flops, nbytes = cost.step_cost_for(CFG)([1])
    embed = 92544 * 2048 * 2
    assert (MM + NORMS) * 2 + 92544 * 2048 * 2 == 3778220032  # PR 11's count
    assert nbytes == 3778220032 - embed + 2048 * 2 + 1 * KV_TOKEN
    assert flops == 2 * MM + 4 * 24 * 16 * 128 * 1


def test_decode_step_at_actual_lengths():
    ctxs = [100, 700, 2047]
    flops, nbytes = cost.step_cost_for(CFG)(ctxs)
    assert flops == 3 * 2 * MM + 4 * 24 * 16 * 128 * sum(ctxs)
    assert nbytes == (MM + NORMS) * 2 + 3 * 2048 * 2 + sum(ctxs) * KV_TOKEN


def test_least_time_names_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    t, bound = peaks.least_time(*cost.step_cost_for(CFG)([512] * 16), v5e)
    assert bound == "memory"
    assert t == pytest.approx(cost.step_cost_for(CFG)([512] * 16)[1] / 819e9)
    assert peaks.least_time(197e12, 1.0, v5e) == (1.0, "compute")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
