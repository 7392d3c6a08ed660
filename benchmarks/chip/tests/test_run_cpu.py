"""One whole run of each cell on the CPU at a tiny size, without the
harness's look for a chip; and the same run with the timed path broken
underneath, which has to come out not correct."""

import dataclasses
import json

import jax.numpy as jnp
import pytest

import correctness
import harness
import run


@pytest.fixture
def fast_arrivals(monkeypatch):
    """Open-loop arrivals at 1 per second, so a short CPU window holds a
    few requests."""
    load = harness.load_cell

    def load_cell(*args, **kw):
        cell = load(*args, **kw)
        if cell.mix["loop"] == "open":
            cell = dataclasses.replace(cell, mix=dict(cell.mix, rate=1.0))
        return cell
    monkeypatch.setattr(harness, "load_cell", load_cell)


def _run(capsys, root, workload, seed=1234567890123, seconds=2.0, trace=0):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])


def test_no_tpu_no_result(tiny_root, capsys):
    rc = run.main(["--workload", "internlm2.decode_batch", "--seed", "1",
                   "--seconds", "1"], root=tiny_root)
    assert rc != 0
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("workload", ["internlm2.decode_batch",
                                      "internlm2.chat_open"])
def test_cell_runs_correct(tiny_root, cpu_peaks, fast_arrivals, capsys,
                           workload):
    rc, res = _run(capsys, tiny_root, workload, seconds=4.0)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_logit_gap"]["value"] < 0.1
    assert {"setup_s", "output_tok_s", "itl_p95_ms"} <= set(res["metrics"])
    assert ("ttft_p95_ms" in res["metrics"]) == workload.endswith("chat_open")


def test_traced_run_reports_host_side_layers(tiny_root, cpu_peaks, capsys):
    rc, res = _run(capsys, tiny_root, "internlm2.decode_batch", trace=1)
    assert rc == 0 and res["correct"] is True
    assert 0 < res["metrics"]["sched.occupancy.batch"]["value"] <= 100
    assert 0 < res["metrics"]["step.mfu.batch"]["value"] < 100


def _state_unchanged(decode_step):
    def broken(params, cfg, tokens, cache, embeds=None):
        logits, _ = decode_step(params, cfg, tokens, cache, embeds)
        return logits, cache
    return broken


def _token_altered(decode_step):
    def broken(params, cfg, tokens, cache, embeds=None):
        logits, new = decode_step(params, cfg, tokens, cache, embeds)
        return logits.at[:, 7].set(jnp.max(logits) + 1.0), new
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
@pytest.mark.parametrize("workload", ["internlm2.decode_batch",
                                      "internlm2.chat_open"])
def test_broken_step_is_not_correct(tiny_root, cpu_peaks, fast_arrivals,
                                    capsys, monkeypatch, fault, workload):
    from repro.models import transformer as T
    monkeypatch.setattr(T, "decode_step", fault(T.decode_step))
    rc, res = _run(capsys, tiny_root, workload, seconds=4.0)
    gap = res["checks"]["widest_logit_gap"]
    assert rc == 0 and res["correct"] is False
    assert gap["limit"] < gap["value"] < correctness.NOTHING_SERVED
