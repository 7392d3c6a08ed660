"""Each slot's recurrent (SSM) state is its own, on the served path.

``ServingEngine`` at 3 slots serves 6 requests of unequal lengths, after a
warm-up that leaves every slot's state dirty.  Each admission replays a
prompt while the other slots hold live state, and each finished request's
slot is reused.  Every row of logits the engine computes for a request
(prompt replay, its first logits, then decoding through the cache) is held
to a float32 full-sequence ``forward`` of that request alone.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro import configs as C
from repro.models import forward, init_params
from repro.serving.engine import ServingEngine

RNG = jax.random.PRNGKey(3)
# (prompt, output) lengths; arrivals a microsecond apart, so each request
# is due before the engine next admits and the order is the list's
REQUESTS = [(5, 9), (3, 4), (7, 6), (4, 8), (6, 3), (2, 7)]
# float32 program against a float32 forward pass of the same weights, as
# a share of the largest logit: they differ by summation order (the
# chunked SSD scan against the step-by-step recurrence), up to 5.7e-7 as
# measured.  Advancing every slot's state each step, or never resetting a
# reused slot's, moves them by 0.13 to 1.5.
TOL = 1e-4


@pytest.mark.parametrize("arch", ["granite_4_0_h_micro", "mamba2_2_7b",
                                  "zamba2_7b"])
def test_engine_serves_each_slot_its_own_state(arch, served_rows):
    cfg = dataclasses.replace(C.get_reduced(arch), dtype="float32")
    params = init_params(RNG, cfg)
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32)
    # warm-up, as the chip benchmark's: one two-token request per slot
    eng.run([{"rid": -1 - i, "arrival": 0.0, "gen_len": 2,
              "prompt": np.full(2, 1 + i, np.int32)} for i in range(3)])
    rng = np.random.default_rng(11)
    reqs = [{"rid": i, "arrival": i * 1e-6, "gen_len": g,
             "prompt": rng.integers(1, cfg.vocab_size, size=p,
                                    dtype=np.int32)}
            for i, (p, g) in enumerate(REQUESTS)]
    rows, results = served_rows(eng, reqs)
    assert sorted(r.rid for r in results) == list(range(len(REQUESTS)))
    assert len(rows) == sum(p + g - 1 for p, g in REQUESTS)
    worst = 0.0
    for r in results:
        req = reqs[r.rid]
        seq = np.concatenate([req["prompt"], r.tokens])[:-1]
        want = np.asarray(forward(params, cfg, tokens=seq[None])[0])
        scale = np.abs(want).max()
        first = len(req["prompt"]) - 1
        np.testing.assert_allclose(r.first_logits, want[first], rtol=0,
                                   atol=TOL * scale)
        for pos in range(len(seq)):
            err = np.abs(rows[r.rid, pos] - want[pos]).max() / scale
            worst = max(worst, float(err))
    assert worst < TOL, worst
