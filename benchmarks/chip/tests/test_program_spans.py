"""Idle gaps labelled by the program's own spans (``repro.serving.telemetry``)
where they nest inside the probe's: the innermost span names the gap."""

import pytest

import harness
import tracereduce as tr
from repro.serving import telemetry
from tracereduce import Event


def test_program_names_are_apart_from_the_probes():
    assert not set(telemetry.SPAN_NAMES) & set(harness.SPANS)
    assert tr.WINDOW not in telemetry.SPAN_NAMES


def test_program_span_inside_probe_span_labels_the_gap():
    # one prompt replay of two tokens, then one decode step, as the
    # probe's wrappers and the engine's spans nest around each other
    host = [Event(tr.WINDOW, 0.0, 20.0),
            Event("sched.admit", 0.0, 10.0),
            Event("engine.prefill", 0.2, 9.8),
            Event("prefill", 0.3, 9.7),
            Event("engine.prefill.sync", 0.4, 1.0),
            Event("prefill.step", 1.1, 1.9),
            Event("engine.prefill.sync", 2.0, 5.0),
            Event("prefill.step", 5.1, 5.9),
            Event("engine.prefill.sync", 6.0, 9.6),
            Event("engine.decode", 10.0, 20.0),
            Event("engine.decode.call", 10.5, 15.0),
            Event("decode.step", 10.6, 14.9),
            Event("engine.decode.sync", 15.0, 17.0)]
    ops = {"/device:TPU:0": [Event("fusion.1", 1.5, 4.0),
                             Event("fusion.1", 5.5, 8.0),
                             Event("fusion.1", 11.0, 15.5)]}
    r = tr.reduce(tr.Trace(ops, {}, host), "jit__step")
    idle = dict(r["idle_gaps"])
    # [0, 1.5): midpoint 0.75 in the length reset; [4, 5.5) and [8, 11)
    # in the per-token syncs (midpoints 4.75 and 9.5); [15.5, 20) in the
    # decode iteration after its fix-up (midpoint 17.75)
    assert idle == {"engine.prefill.sync": pytest.approx(1.5 + 1.5 + 3.0),
                    "engine.decode": pytest.approx(4.5)}
    label = tr.Labels(host)
    assert label(9.65) == "prefill"                 # probe span innermost
    assert label(14.95) == "engine.decode.call"
    assert label(16.0) == "engine.decode.sync"
    assert label(19.5) == "engine.decode"
