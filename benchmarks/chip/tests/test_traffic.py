import json
import pathlib

import numpy as np
import pytest

import traffic

HERE = pathlib.Path(traffic.__file__).resolve().parent
MIXES = {n: json.loads((HERE / "traffic" / f"{n}.json").read_text())
         for n in ("decode_batch", "chat_open")}
KW = dict(seconds=51, vocab=92544, max_len=2048, max_batch=16)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_gets_the_same_work(name):
    a = traffic.build(MIXES[name], seed=1, **KW)
    b = traffic.build(MIXES[name], seed=2 ** 40 + 3, **KW)
    assert [(r["arrival"], len(r["prompt"]), r["gen_len"]) for r in a] == \
        [(r["arrival"], len(r["prompt"]), r["gen_len"]) for r in b]
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    c = traffic.build(MIXES[name], seed=1, **KW)
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, c))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_fit_the_engine(name):
    mix = MIXES[name]
    for r in traffic.build(mix, seed=5, **KW):
        p = len(r["prompt"])
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert 1 <= r["gen_len"] and p + r["gen_len"] <= KW["max_len"] - 1
        assert r["prompt"].min() >= 1 and r["prompt"].max() < KW["vocab"]


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    mix = MIXES["chat_open"]
    reqs = traffic.build(mix, seed=0, **KW)
    arrivals = [r["arrival"] for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
    assert arrivals[-1] < KW["seconds"]
    assert len(reqs) == int(mix["rate"] * KW["seconds"])


def test_closed_loop_fill_then_backlog_all_due_at_once():
    mix = MIXES["decode_batch"]
    reqs = traffic.build(mix, seed=0, **KW)
    assert len(reqs) == KW["max_batch"] + mix["backlog"]
    assert {r["arrival"] for r in reqs} == {0.0}


def test_quantile_sets_keep_the_distribution_mean():
    # the chat output grid over 400 requests: log-normal mean 189.47
    mix = MIXES["chat_open"]
    sched = traffic.schedule(dict(mix, rate=8.0), seconds=50, max_len=10 ** 6,
                             max_batch=1)
    outs = [g for _, _, g in sched]
    assert np.mean(outs) == pytest.approx(189.47, rel=0.05)
    assert sorted(outs[:40]) != outs[:40]         # order is spread, not sorted
