"""Whether what the timed path served is right.

Once the window has closed and the program's state is freed, a sample of
the served requests, drawn from the seed and always holding the one with
the most served tokens, goes through the configuration's plain float32
reference (``configs/<name>.py``, ``Reference``) with each prompt followed
by its served tokens.  At every served token the reference's best logit
minus its logit for the served token is that token's gap; the run's
number is the widest gap.  A greedy server that computes what the
reference computes serves, at every step, a token within rounding of the
reference's best.

Closed loop: the sample is drawn from every request that has served a
token, finished or still in flight when the window closed (in flight, its
tokens so far).  Open loop: from the finished requests; a request due in
the window that never finished is counted as unfinished.
"""

from __future__ import annotations

from typing import List

import numpy as np

import traffic as traffic_mod

NOTHING_SERVED = 1e9      # the widest gap of a run that served no token


def sample(reqs, n: int, seed: int, loop: str) -> list:
    cands = [r for r in reqs
             if r.slot is not None and (r.done or loop == "closed")]
    if not cands:
        return []
    longest = max(cands, key=lambda r: len(r.tokens))
    rest = [r for r in cands if r is not longest]
    rng = traffic_mod.seed_rng(seed, 1)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def token_rows(seqs, n_rows: int, pad_len: int):
    """Reference inputs for (prompt, served) pairs: each row is the prompt
    and the served tokens but the last; ``where`` lists (row, position) of
    the position that predicts each served token, ``targets`` the token."""
    tokens = np.zeros((n_rows, pad_len), np.int32)
    where, targets = [], []
    for row, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, np.asarray(served, np.int32)])[:-1]
        tokens[row, :len(seq)] = seq
        for j, tok in enumerate(served):
            where.append((row, len(prompt) - 1 + j))
            targets.append(tok)
    return tokens, np.array(where, np.int32).reshape(-1, 2), \
        np.array(targets, np.int32)


def gaps(model, cfg: dict, seed: int, seqs, n_rows: int, pad_len: int,
         control: bool = False) -> dict:
    """The served tokens' gaps below the reference's best logit; with
    ``control``, also the gaps of the tokens the float8 control puts
    first at the same positions."""
    ref = model.Reference(cfg, seed)
    tokens, where, targets = token_rows(seqs, n_rows, pad_len)
    h = ref.hidden(tokens)
    rows = h[where[:, 0], where[:, 1]]
    out = {"tokens": len(targets)}
    if control:
        hq = ref.hidden(tokens, quant=True)
        best, at_target, at_pick = ref.logit_stats(
            rows, targets, hq[where[:, 0], where[:, 1]])
        out["control"] = best - at_pick
    else:
        best, at_target = ref.logit_stats(rows, targets)
    out["served"] = best - at_target
    return out


def check(cell, out, seed: int) -> List[dict]:
    """The numbers compared, each with its limit; the run is correct when
    none exceeds its limit."""
    reqs = [r for r in out.probe.requests.values()]
    prompts = {r["rid"]: r["prompt"] for r in out.requests}
    vocab = cell.cfg["vocab_size"]
    loop = cell.mix["loop"]
    unfinished = sum(1 for r in reqs if not r.done) if loop == "open" else 0
    bad_ids = sum(1 for r in reqs for t in r.tokens if not 0 <= t < vocab)
    picked = sample(reqs, cell.mix["sample"], seed, loop)
    seqs = [(prompts[r.rid], r.tokens) for r in picked]
    widest, n_tokens = NOTHING_SERVED, 0
    if seqs:
        g = gaps(cell.model, cell.cfg, seed, seqs, cell.mix["sample"],
                 out.max_len)
        widest, n_tokens = float(g["served"].max()), g["tokens"]
    limit = cell.cfg["correct"]["widest_logit_gap"]
    return [
        {"name": "widest_logit_gap", "value": widest, "limit": limit,
         "tokens": n_tokens, "requests": len(seqs)},
        {"name": "unfinished", "value": unfinished, "limit": 0},
        {"name": "ids_out_of_range", "value": bad_ids, "limit": 0},
    ]
