"""The one traffic generator: a mix's data file in, the run's requests out.

A mix (``traffic/<name>.json``) gives the prompt and output length
distributions (log-normal by mean and standard deviation, as
``repro.core.trace`` fits them to the APEX paper's traces, then clipped),
the loop and its size:

* "closed": ``max_batch + backlog`` requests, all due at 0, so the engine
  keeps every slot busy; the first ``max_batch`` fill the slots;
* "open": ``floor(rate * seconds)`` requests with exponential gaps at
  ``rate`` per second, all due in [0, seconds).

Every seed gets the same work.  Each of the n prompt lengths, output
lengths and gaps is a quantile (i + 0.5) / n of its distribution, so the
set has the distribution's shape at every n.  Request j takes the quantile
whose rank is that of the j-th point of a Halton sequence (bases 2, 3 and
5; one base per quantity, so the three are paired evenly): the first k
requests are then representative of the mix for every k, and which
requests a window reaches does not depend on the seed either.  The seed draws the
prompt token ids (and, elsewhere, the weights and the correctness sample).
Prompt + output stays below the engine's ``max_len``.
"""

from __future__ import annotations

import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


def halton(j: int, base: int) -> float:
    """The j-th (j >= 1) point of the van der Corput sequence in ``base``."""
    x, f = 0.0, 1.0
    while j:
        f /= base
        x += f * (j % base)
        j //= base
    return x


def ranked_quantiles(n: int, base: int) -> List[float]:
    """(rank + 0.5) / n for the rank of each of the first n Halton points."""
    ranks = np.argsort(np.argsort([halton(j, base) for j in range(1, n + 1)]))
    return [(r + 0.5) / n for r in ranks]


def lognormal_at(u: float, mean: float, std: float) -> float:
    sigma2 = math.log(1.0 + (std / mean) ** 2)
    mu = math.log(mean) - sigma2 / 2.0
    return math.exp(mu + math.sqrt(sigma2) * _NORMAL.inv_cdf(u))


def length_at(u: float, dist: dict) -> int:
    x = round(lognormal_at(u, dist["mean"], dist["std"]))
    return int(min(max(x, dist.get("min", 1)), dist.get("max", 1 << 30)))


def seed_rng(seed: int, stream: int):
    return np.random.default_rng([seed % 2 ** 64, stream])


def schedule(mix: dict, *, seconds: float, max_len: int, max_batch: int):
    """(arrival, prompt length, output length) of every request."""
    if mix["loop"] == "closed":
        n = max_batch + mix["backlog"]
    elif mix["loop"] == "open":
        n = int(math.floor(mix["rate"] * seconds))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    out, t = [], 0.0
    for u, v, w in zip(*(ranked_quantiles(n, b) for b in (2, 3, 5))):
        prompt = min(length_at(u, mix["prompt"]), max_len - 2)
        gen = min(length_at(v, mix["output"]), max_len - 1 - prompt)
        out.append((t if mix["loop"] == "open" else 0.0, prompt, gen))
        t += -math.log(1.0 - w) / mix.get("rate", 1.0)
    return [r for r in out if r[0] < seconds]


def build(mix: dict, *, seed: int, seconds: float, vocab: int, max_len: int,
          max_batch: int) -> List[dict]:
    """Requests as ``ServingEngine.run`` takes them: rid, arrival (seconds
    from the schedule's start), prompt (int32 ids), gen_len."""
    ids = seed_rng(seed, 0)
    return [{"rid": i, "arrival": a,
             "prompt": ids.integers(1, vocab, size=p, dtype=np.int32),
             "gen_len": g}
            for i, (a, p, g) in enumerate(schedule(
                mix, seconds=seconds, max_len=max_len, max_batch=max_batch))]
