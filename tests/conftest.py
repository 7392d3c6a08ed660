import math
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str, devices: int = 8, timeout: int = 420):
    """Run a test body in a fresh interpreter with N host devices.

    Multi-device shard_map/pjit tests need
    --xla_force_host_platform_device_count, which must be set before jax
    initializes — impossible in the already-running pytest process.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{res.stdout}\nSTDERR:\n"
            f"{res.stderr[-4000:]}")
    return res.stdout


@pytest.fixture
def subproc():
    return run_subprocess


# tests/golden/core_golden.json was captured on Python 3.10.  Python 3.12's
# float sum() is compensated (Neumaier), so a mean over per-request floats
# (core/metrics.py: sum(xs) / len(xs)) can move by an ulp or two.  The
# per-request records, percentiles and every other field are single
# arithmetic chains and must still match exactly; only the means get a
# relative tolerance, far below any real behavioural change.
GOLDEN_MEAN_FIELDS = ("ttft_mean", "tpot_mean")
GOLDEN_MEAN_REL_TOL = 1e-12


def assert_report_matches(rep, want):
    for field, expect in want.items():
        if field == "records":
            got = sorted((r.rid, r.first_token_time, r.finish_time,
                          r.preemptions, r.refetch_s) for r in rep.records)
            assert got == [tuple(r) for r in expect]
        elif field in GOLDEN_MEAN_FIELDS:
            assert math.isclose(getattr(rep, field), expect,
                                rel_tol=GOLDEN_MEAN_REL_TOL), field
        else:
            assert getattr(rep, field) == expect, field


@pytest.fixture
def report_matches():
    """Golden-report comparison (exact except the means, see above)."""
    return assert_report_matches


def engine_rows(eng, requests):
    """Serve ``requests`` on the ServingEngine ``eng``: every row of logits
    its step computes for a request it serves (prompt replay, then
    decoding), keyed (rid, position of the fed token), and the report's
    results."""
    step, prefill = eng._decode, eng._prefill_slot
    replaying, rows = [], {}

    def prefill_slot(i):
        replaying.append(i)
        try:
            prefill(i)
        finally:
            replaying.pop()

    def decode(p, toks, cache):
        lens = np.array(cache["len"])       # the step consumes the cache
        nxt, logits, new = step(p, toks, cache)
        slots = replaying[-1:] or [i for i, s in enumerate(eng.slots)
                                   if s.active]
        for i in slots:
            rows[eng.slots[i].rid, int(lens[i])] = np.asarray(logits[i])
        return nxt, logits, new

    eng._prefill_slot, eng._decode = prefill_slot, decode
    try:
        return rows, eng.run(requests).results
    finally:
        eng._prefill_slot, eng._decode = prefill, step


@pytest.fixture
def served_rows():
    return engine_rows
