"""The correctness control at a size a CPU test can hold: the float32
reference computed with float8 e4m3 weights and activations, at the
positions the program served.  Its widest gap has to stand well clear of
the program's (the full-size readings that set the limit come from
``calibrate.py`` on the chip; see PERF.md)."""

import json
import time

import jax
import pytest

import correctness
import harness
from conftest import make_root

SMALL = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=4096,
             serving={"max_batch": 4, "max_len": 128})


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 1])
def test_control_gap_stands_clear_of_the_served_gap(tmp_path, seed):
    root = make_root(tmp_path, **SMALL)
    cell = harness.load_cell(json.loads((root / "BENCHMARK.json").read_text()),
                             "internlm2.decode_batch", root)
    out = harness.serve(cell, seed=seed, seconds=2.0, trace=False,
                        t_start=time.perf_counter(), jax=jax,
                        device=jax.devices()[0])
    prompts = {r["rid"]: r["prompt"] for r in out.requests}
    picked = correctness.sample(list(out.probe.requests.values()), 4, seed,
                                "closed")
    g = correctness.gaps(cell.model, cell.cfg, seed,
                         [(prompts[r.rid], r.tokens) for r in picked], 4,
                         out.max_len, control=True)
    assert g["tokens"] > 50
    assert g["control"].max() >= 3 * g["served"].max()
    assert g["served"].max() <= cell.cfg["correct"]["widest_logit_gap"]
