"""Runs one cell once: the program's ``ServingEngine.run`` driven by the
cell's traffic, measured from outside.

``Probe`` wraps three attributes of one engine instance and nothing in the
program's source: ``_admit`` (the scheduler), ``_prefill_slot`` (the prompt
replay) and ``_decode`` (the jitted step).  Around them it

* keeps the engine's clock on the wall clock: the engine's virtual clock
  advances only inside timed blocks and, when idle, jumps to the next
  arrival; the probe sleeps until that arrival is due and hands the engine
  the wall-clock time since the schedule's start, so an open loop really
  waits and requests are admitted when they are due;
* stamps every output token on the host clock once the step's tokens are
  on the host (first tokens when their prefill returns);
* opens and closes the measured window, and the traced part of it;
* opens a ``jax.profiler.TraceAnnotation`` around each call, so the trace
  can say what the host was doing in each idle gap.

Closed loop: the window opens when the fill has taken every slot and
closes ``seconds`` later; the run is stopped there.  Open loop: the window
is [0, seconds) of the schedule; requests due in it are drained after it,
for at most the mix's ``drain_seconds``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import cost
import peaks as peaks_mod
import traffic as traffic_mod
import tracereduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]                    # the checkout
TRACE_DIR = ROOT / ".bench_trace"
STEP_MODULE = "jit__step"                 # ServingEngine's jitted step
SPANS = ("sched.admit", "prefill", "prefill.step", "decode.step",
         "wait.arrival")
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class WindowClosed(Exception):
    """Raised inside ``ServingEngine.run`` to stop it at the window's end
    (closed loop) or at the drain's deadline (open loop)."""


@dataclasses.dataclass
class Req:
    rid: int
    due: float                     # host clock
    prompt_len: int
    gen_len: int
    admitted: Optional[float] = None
    first: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    slot: object = None            # the engine's _Slot; its .tokens

    @property
    def tokens(self) -> List[int]:
        return list(self.slot.tokens) if self.slot is not None else []

    @property
    def done(self) -> bool:
        return len(self.times) >= self.gen_len


@dataclasses.dataclass(frozen=True)
class Call:
    start: float
    mode: str                      # "decode" or "prefill"
    ctxs: Tuple[int, ...]          # cache entries each useful token reads


class CompileCounter:
    """Programs compiled or read from the persistent cache, with when."""

    def __init__(self, jax):
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


class Probe:
    def __init__(self, engine, jax, *, loop: str, seconds: float,
                 drain_seconds: float = 0.0, trace_seconds: float = 0.0):
        self.engine, self.jax = engine, jax
        self.loop, self.seconds = loop, seconds
        self.drain_seconds = drain_seconds
        self.trace_seconds = trace_seconds
        self._admit0 = engine._admit
        self._prefill0 = engine._prefill_slot
        self._decode0 = engine._decode
        engine._admit = self._admit
        engine._prefill_slot = self._prefill
        engine._decode = self._decode
        self.requests: Dict[int, Req] = {}
        self.calls: List[Call] = []
        self.lateness: List[float] = []
        self.t0 = self.ws = self.we = None
        self.traced: Optional[Tuple[float, float]] = None
        self._trace_on = None
        self._replay: Optional[int] = None

    # -- the run ---------------------------------------------------------------

    def run(self, requests: List[dict]) -> None:
        self.t0 = time.perf_counter()
        for r in requests:
            self.requests[r["rid"]] = Req(r["rid"], self.t0 + r["arrival"],
                                          len(r["prompt"]), r["gen_len"])
        if self.loop == "open":
            self._open_window(self.t0)
        try:
            self.engine.run(requests)
        except WindowClosed:
            pass
        finally:
            self._stop_trace()

    def _open_window(self, start: float) -> None:
        self.ws, self.we = start, start + self.seconds
        if self.trace_seconds:
            TRACE_DIR.mkdir(exist_ok=True)
            options = self.jax.profiler.ProfileOptions()
            options.python_tracer_level = 0      # the probe's spans suffice
            self.jax.profiler.start_trace(str(TRACE_DIR),
                                          profiler_options=options)
            self._trace_on = self.jax.profiler.TraceAnnotation(
                tracereduce.WINDOW)
            self._trace_on.__enter__()
            self.traced = (time.perf_counter(), None)

    def _stop_trace(self) -> None:
        if self._trace_on is None:
            return
        self.traced = (self.traced[0], time.perf_counter())
        self._trace_on.__exit__(None, None, None)
        self._trace_on = None
        self.jax.profiler.stop_trace()

    def release(self) -> None:
        """Drop every reference to the engine (its weights and cache)."""
        self.engine = self._admit0 = self._prefill0 = self._decode0 = None

    def _since0(self) -> float:
        return time.perf_counter() - self.t0

    # -- wrappers ----------------------------------------------------------------

    def _admit(self, now: float) -> float:
        t = self._since0()
        if now > t:                     # idle: the next request is not due yet
            with self.jax.profiler.TraceAnnotation("wait.arrival"):
                time.sleep(now - t)
            self.lateness.append(self._since0() - now)
        clock = time.perf_counter()
        if self._trace_on is not None and \
                clock >= self.traced[0] + self.trace_seconds:
            self._stop_trace()
        if self.ws is not None:
            deadline = self.we if self.loop == "closed" \
                else self.we + self.drain_seconds
            if clock >= deadline:
                raise WindowClosed
        with self.jax.profiler.TraceAnnotation("sched.admit"):
            self._admit0(self._since0())
        if self.ws is None and all(s.active for s in self.engine.slots):
            self._open_window(time.perf_counter())   # closed: fill done
        return self._since0()

    def _prefill(self, i: int) -> None:
        slot = self.engine.slots[i]
        req = self.requests[slot.rid]
        req.admitted = time.perf_counter()
        self._replay = 0
        try:
            with self.jax.profiler.TraceAnnotation("prefill"):
                self._prefill0(i)
        finally:
            self._replay = None
        req.first = time.perf_counter()
        req.times.append(req.first)
        req.slot = slot

    def _decode(self, params, toks, cache):
        start = time.perf_counter()
        if self._replay is not None:
            self._replay += 1
            with self.jax.profiler.TraceAnnotation("prefill.step"):
                out = self._decode0(params, toks, cache)
            self.calls.append(Call(start, "prefill", (self._replay,)))
            return out
        active = [s for s in self.engine.slots if s.active]
        ctxs = tuple(s.kv_tokens for s in active)
        with self.jax.profiler.TraceAnnotation("decode.step"):
            nxt, logits, cache = self._decode0(params, toks, cache)
            nxt = np.asarray(self.jax.device_get(nxt))
        end = time.perf_counter()
        for s in active:
            self.requests[s.rid].times.append(end)
        self.calls.append(Call(start, "decode", ctxs))
        return nxt, logits, cache


# ---------------------------------------------------------------------------
# one cell, one seed
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    cfg: dict                      # the configuration file's JSON
    model: object                  # configs/<name>.py
    mix: dict                      # traffic/<name>.json
    traffic_name: str
    end_to_end: List[dict]         # metric entries this cell reports
    per_layer: List[dict]


def load_cell(bench: dict, workload: str, root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = root / conf["file"]
    cfg = json.loads(cfg_path.read_text())
    model = cost.load_module(cfg_path.with_suffix(".py"))
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(workload, w["config"], cfg, model, mix, w["traffic"], e2e,
                per_layer)


@dataclasses.dataclass
class Outcome:
    probe: Probe
    requests: List[dict]
    setup_s: float
    memory_peak_bytes: Optional[int]
    compiles_in_window: int
    trace: Optional[dict]
    device: object
    max_batch: int
    max_len: int


def warm_requests(max_batch: int, vocab: int) -> List[dict]:
    """One two-token prompt per slot, two tokens out: every program the
    window runs (the step, the per-slot logits slice) compiles or loads
    here."""
    return [{"rid": -1 - i, "arrival": 0.0,
             "prompt": np.full(2, 1 + i, np.int32), "gen_len": 2}
            for i in range(max_batch)]


def serve(cell: Cell, *, seed: int, seconds: float, trace: bool,
          t_start: float, jax, device) -> Outcome:
    """Weights from the seed, the engine, warm-up, then the measured run."""
    from repro.serving.engine import ServingEngine
    serving = cell.cfg["serving"]
    max_batch, max_len = serving["max_batch"], serving["max_len"]
    compiles = CompileCounter(jax)
    params = jax.block_until_ready(cell.model.make_weights(seed, cell.cfg))
    engine = ServingEngine(cell.model.program_config(cell.cfg), params,
                           max_batch=max_batch, max_len=max_len)
    del params
    engine.run(warm_requests(max_batch, cell.cfg["vocab_size"]))
    requests = traffic_mod.build(cell.mix, seed=seed, seconds=seconds,
                                 vocab=cell.cfg["vocab_size"],
                                 max_len=max_len, max_batch=max_batch)
    probe = Probe(engine, jax, loop=cell.mix["loop"], seconds=seconds,
                  drain_seconds=cell.mix.get("drain_seconds", 0.0),
                  trace_seconds=cell.mix["trace_seconds"] if trace else 0.0)
    probe.run(requests)
    stats = device.memory_stats() or {}
    reduced = None
    if trace:
        reduced = tracereduce.reduce(
            tracereduce.load(TRACE_DIR, SPANS + (tracereduce.WINDOW,)),
            STEP_MODULE)
    # free the program's state before the reference runs
    probe.release()
    del engine
    gc.collect()
    return Outcome(probe, requests, probe.ws - t_start,
                   stats.get("peak_bytes_in_use"),
                   compiles.between(probe.ws, probe.we), reduced, device,
                   max_batch, max_len)


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """Everything a reader in ``metrics/`` may read.  ``window`` is the
    measured window and ``span`` the part of it the per-layer metrics
    cover: the traced part in a traced run, else the whole window (both
    host clock)."""
    setup_s: float
    window: Tuple[float, float]
    span: Tuple[float, float]
    requests: List[Req]
    calls: List[Call]
    trace: Optional[dict]
    step_cost: object              # ctxs -> (flops, bytes)
    peaks: peaks_mod.Peaks
    max_batch: int


def context(cell: Cell, out: Outcome) -> Context:
    p = out.probe
    span = p.traced if p.traced is not None else (p.ws, p.we)
    return Context(out.setup_s, (p.ws, p.we), span,
                   sorted(p.requests.values(), key=lambda r: r.rid), p.calls,
                   out.trace, cost.step_cost_for(cell.cfg),
                   peaks_mod.peaks_for(out.device.device_kind), out.max_batch)


def read_metrics(entries: List[dict], ctx: Context) -> dict:
    out = {}
    for m in entries:
        reader = cost.load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
