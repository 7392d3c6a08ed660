import json
import pathlib

import pytest

import tracereduce as tr
from tracereduce import Event

HERE = pathlib.Path(__file__).resolve().parent


def test_union_gaps_and_clip():
    busy = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]


def test_label_is_innermost_span_not_the_window():
    spans = [Event(tr.WINDOW, 0, 10), Event("prefill", 1, 5),
             Event("prefill.step", 2, 3)]
    label = tr.Labels(spans)
    assert label(2.5) == "prefill.step"
    assert label(4.0) == "prefill"
    assert label(7.0) == tr.UNLABELLED
    assert label(0.5) == tr.UNLABELLED
    labels = tr.Labels(spans + [Event("decode.step", 6.0, 8.0)])
    assert [labels(t) for t in (1.5, 2.5, 3.5, 6.5, 9.0)] == [
        "prefill", "prefill.step", "prefill", "decode.step", tr.UNLABELLED]


def test_reduce_synthetic_two_devices():
    host = [Event(tr.WINDOW, 0.0, 10.0), Event("decode.step", 0.0, 4.0),
            Event("wait.arrival", 6.0, 10.0)]
    ops = {"/device:TPU:0": [Event("fusion.1", 1.0, 3.0),
                             Event("fusion.2", 3.0, 4.0)],
           "/device:TPU:1": [Event("fusion.1", 1.0, 2.0),
                             Event("copy.3", 9.0, 12.0)]}
    mods = {"/device:TPU:0": [Event("jit__step(1)", 1.0, 4.0),
                              Event("jit_other", 5.0, 6.0)]}
    r = tr.reduce(tr.Trace(ops, mods, host), "jit__step")
    # busy: device 0 [1,4] = 3 s; device 1 [1,2] + [9,10] = 2 s
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["window_s"] == 10.0
    assert r["idle_share"] == pytest.approx(0.75)
    assert r["step_device_s"] == [3.0]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    # idle, averaged over the two devices: device 0 [0,1] (decode.step)
    # and [4,10] (midpoint in wait.arrival); device 1 [0,1] (decode.step)
    # and [2,9] (midpoint 5.5, under no span)
    idle = dict(r["idle_gaps"])
    assert idle["wait.arrival"] == pytest.approx(3.0)
    assert idle["decode.step"] == pytest.approx(1.0)
    assert idle[tr.UNLABELLED] == pytest.approx(3.5)


def test_reduce_without_window_or_ops_is_none():
    assert tr.reduce(tr.Trace({}, {}, [Event(tr.WINDOW, 0, 1)]), "x") is None
    assert tr.reduce(tr.Trace({"/device:TPU:0": [Event("a", 0, 1)]}, {}, []),
                     "x") is None


def test_nested_ops_count_self_time_under_short_names():
    assert tr.short_name("%while.15 = (s32[]) while(...)") == "while.15"
    ops = [Event("while.15", 0.0, 10.0), Event("fusion.1", 1.0, 4.0),
           Event("copy.2", 2.0, 3.0), Event("fusion.1", 5.0, 6.0)]
    t = tr.self_times(ops, 0.0, 10.0)
    assert t == pytest.approx({"while.15": 6.0, "fusion.1": 3.0, "copy.2": 1.0})
    assert sum(t.values()) == pytest.approx(10.0)


def test_recorded_v5e_trace():
    fx = json.loads((HERE / "trace_v5e_step.json").read_text())
    ev = lambda rows: [Event(*r) for r in rows]
    w0, w1 = fx["window"]
    trace = tr.Trace({"/device:TPU:0": ev(fx["ops"])},
                     {"/device:TPU:0": ev(fx["modules"])},
                     ev(fx["host"]) + [Event(tr.WINDOW, w0, w1)])
    r = tr.reduce(trace, "jit__step")
    # two whole executions of the step lie in the window, 57.8 ms each
    assert r["step_device_s"] == pytest.approx([0.057843406, 0.057826816])
    assert r["window_s"] == pytest.approx(0.13)
    # the step runs back to back: the device idles a few ms per step,
    # while the host fetches the tokens and admits
    assert 0.02 < r["idle_share"] < 0.15
    assert r["busy_s"] + sum(t for _, t in r["idle_gaps"]) == \
        pytest.approx(r["window_s"])
    assert {n for n, _ in r["idle_gaps"]} <= {"decode.step", "sched.admit",
                                              tr.UNLABELLED}
    # self times: no op's time counts twice
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] + 1e-9
    assert r["device_ops"][0][1] > 0.02
