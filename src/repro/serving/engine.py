"""Continuous-batching serving engine (real execution, any backend).

Implements iteration-level batching over a slot-based KV cache:

  * ``max_batch`` slots share one cache pytree; each slot holds one active
    request (its KV rows + length counter);
  * admission is greedy on free slots AND free KV-token budget — exactly
    the Batching Module's policy (core/batching.py), including preemption
    of the most-recently-admitted request when the token budget overflows;
  * each engine iteration runs ONE jitted decode step over all slots
    (inactive slots are masked); ``_prefill_slot``, the one prefill,
    populates a request's slot by replaying its prompt through that step;
  * each slot's recurrent (SSM) state is its own: the host uploads which
    slots a step advances (``advance``, beside the lengths) so that a
    replay or a decode iteration changes only the slots it feeds, and a
    slot whose replay starts has length 0, which the step reads as zero
    state (``transformer.init_cache``);
  * the step is given the cache to consume (``make_decode_step`` donates
    it): it writes each slot's new entry in place and returns the one
    cache buffer, so the engine holds the cache once and drops its
    reference to the old one as soon as a step returns;
  * arrivals are honored in VIRTUAL time: the clock advances by measured
    step wall-times, and a request joins the queue once the virtual clock
    passes its arrival stamp.  This makes CPU-scale fidelity runs directly
    comparable with the simulator's virtual-clock results (Fig. 6/7).

Checkpointable: ``snapshot()``/``restore()`` capture queued + in-flight
request state so a restarted replica replays its work (fault tolerance).

Given an ``EngineTrace`` the engine records a span at each layer boundary
(prompt replay, decode step and their host syncs, eviction); ``telemetry``
names them and their counts.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.telemetry import OFF, EngineTrace


def make_decode_step(cfg: ModelConfig):
    """The engine's jitted step: (params, tokens (B, 1), cache) ->
    (greedy next tokens (B,), logits (B, vocab), new cache).  The cache is
    donated: the step updates it in place, and the cache passed in is
    deleted by the call."""
    def _step(p, t, c):
        logits, c2 = T.decode_step(p, cfg, t, c)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, c2

    return jax.jit(_step, donate_argnums=(2,))


@dataclasses.dataclass
class _Slot:
    rid: int = -1
    prompt: Optional[np.ndarray] = None
    gen_len: int = 0
    generated: int = 0
    order: int = -1
    arrival: float = 0.0
    first_token_t: Optional[float] = None
    first_logits: Optional[np.ndarray] = None
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def active(self) -> bool:
        return self.rid >= 0

    @property
    def kv_tokens(self) -> int:
        if not self.active:
            return 0
        return len(self.prompt) + self.generated


@dataclasses.dataclass
class RequestResult:
    rid: int
    arrival: float
    ttft: float
    tpot: float
    e2e: float
    tokens: List[int]
    preemptions: int = 0
    first_logits: Optional[np.ndarray] = None   # float32 (vocab,)


@dataclasses.dataclass
class EngineReport:
    results: List[RequestResult]
    total_time: float
    iterations: int
    preemptions: int


class ServingEngine:
    """Each ``RequestResult`` carries the request's first-token logits as
    served — what a correctness check compares against a reference forward
    pass.  ``trace``, when given, records the engine's spans.

    ``self.cache`` is the one live cache: every step consumes it (donated,
    see ``make_decode_step``) and the engine takes the step's output in its
    place before anything else runs."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 512, kv_token_budget: Optional[int] = None,
                 trace: Optional[EngineTrace] = None):
        self.cfg = cfg
        self.trace = trace
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.kv_budget = kv_token_budget or (max_batch * max_len)
        self.slots = [_Slot() for _ in range(max_batch)]
        self.cache = T.init_cache(cfg, max_batch, max_len)
        # bytes of recurrent (SSM) state the cache holds, for all slots
        self.state_reserved = sum(
            x.size * x.dtype.itemsize
            for path, x in jax.tree_util.tree_leaves_with_path(self.cache)
            if path[-1].key in ("ssm", "conv_x", "conv_bc"))
        self.queue: List[dict] = []
        self._order = 0
        self.preemptions = 0
        self._decode = make_decode_step(cfg)

    # -- fault tolerance -------------------------------------------------------

    def snapshot(self) -> dict:
        """Scheduler state for checkpoint/restart: queued + in-flight
        requests (in-flight ones will re-prefill after restore)."""
        inflight = [dict(rid=s.rid, prompt=s.prompt, gen_len=s.gen_len,
                         arrival=s.arrival)
                    for s in self.slots if s.active]
        return {"queue": list(self.queue), "inflight": inflight}

    def restore(self, snap: dict) -> None:
        self.queue = list(snap["queue"]) + list(snap["inflight"])
        self.queue.sort(key=lambda r: r["arrival"])
        self.slots = [_Slot() for _ in range(self.max_batch)]
        self.cache = T.init_cache(self.cfg, self.max_batch, self.max_len)

    # -- scheduling ------------------------------------------------------------

    def _kv_used(self) -> int:
        return sum(s.kv_tokens for s in self.slots)

    def _admit(self, now: float) -> float:
        """Admit arrived requests into free slots and prefill each; returns
        the virtual clock after the prefills.  A request's first token is
        stamped when its own prefill ends."""
        tr = self.trace
        while self.queue and self.queue[0]["arrival"] <= now:
            req = self.queue[0]
            free = [i for i, s in enumerate(self.slots) if not s.active]
            if not free:
                break
            if self._kv_used() + len(req["prompt"]) > self.kv_budget:
                break
            self.queue.pop(0)
            i = free[0]
            self.slots[i] = _Slot(rid=req["rid"],
                                  prompt=np.asarray(req["prompt"]),
                                  gen_len=req["gen_len"], order=self._order,
                                  arrival=req["arrival"])
            self._order += 1
            t0 = time.perf_counter()
            with OFF if tr is None else tr.span(
                    "engine.prefill", rid=req["rid"],
                    tokens=len(req["prompt"]),
                    waited_s=now - req["arrival"]):
                self._prefill_slot(i)
            now += time.perf_counter() - t0
            self.slots[i].first_token_t = now
        return now

    def _prefill_slot(self, i: int) -> None:
        """Replay the prompt through the jitted decode step (correctness-
        first prefill; the whole batch's other slots ride along masked).

        The replay starts slot ``i`` at length 0, so its SSM state (if the
        model has any) starts from zero whatever the slot held before, and
        advances only slot ``i``: the other slots' states stand still, and
        their attention lengths are held back.  At the end every active
        slot, ``i`` included, is advanced again."""
        s = self.slots[i]
        tr = self.trace
        with OFF if tr is None else tr.span("engine.prefill.sync"):
            self.cache["len"] = self._lengths(replaying=(i, 0))
            self._set_advance(only=i)
        for t in range(len(s.prompt)):
            toks = np.zeros((self.max_batch, 1), np.int32)
            toks[i, 0] = s.prompt[t]
            logits_tok, logits, self.cache = self._decode(
                self.params, jnp.asarray(toks), self.cache)
            with OFF if tr is None else tr.span("engine.prefill.sync"):
                # only slot i's length may advance; one step at a time
                self.cache["len"] = self._lengths(replaying=(i, t + 1))
                logits_tok.block_until_ready()
        s.generated = 1
        with OFF if tr is None else tr.span("engine.prefill.sync"):
            self._set_advance()
            first = int(jax.device_get(logits_tok)[i])
            s.first_logits = np.asarray(jax.device_get(logits[i]),
                                        np.float32)
        s.tokens.append(first)

    def _evict_most_recent(self) -> None:
        cand = [s for s in self.slots if s.active]
        if not cand:
            return
        victim = max(cand, key=lambda s: s.order)
        tr = self.trace
        with OFF if tr is None else tr.span("engine.evict"):
            idx = self.slots.index(victim)
            self.queue.insert(0, dict(rid=victim.rid, prompt=victim.prompt,
                                      gen_len=victim.gen_len,
                                      arrival=victim.arrival))
            self.preemptions += 1
            self.slots[idx] = _Slot()

    # -- main loop -------------------------------------------------------------

    def run(self, requests: List[dict],
            time_scale: float = 1.0) -> EngineReport:
        """Serve ``requests`` (dicts: rid, arrival, prompt, gen_len).

        ``time_scale`` compresses arrival stamps (CPU runs are slow; the
        fidelity benchmark scales both simulator and engine identically).
        """
        self.queue = sorted(
            (dict(r, arrival=r["arrival"] * time_scale) for r in requests),
            key=lambda r: r["arrival"])
        records: Dict[int, RequestResult] = {}
        now = 0.0
        iters = 0
        tr = self.trace
        while self.queue or any(s.active for s in self.slots):
            now = self._admit(now)
            active = [i for i, s in enumerate(self.slots) if s.active]
            if not active:
                if self.queue:
                    now = max(now, self.queue[0]["arrival"])
                    continue
                break
            with OFF if tr is None else tr.span(
                    "engine.decode", active=len(active),
                    kv_tokens=self._kv_used(),
                    kv_reserved=self.max_batch * self.max_len,
                    state_reserved=self.state_reserved):
                now = self._decode_iteration(active, now, records)
            iters += 1

        return EngineReport(results=list(records.values()), total_time=now,
                            iterations=iters, preemptions=self.preemptions)

    def _decode_iteration(self, active: List[int], now: float,
                          records: Dict[int, RequestResult]) -> float:
        """One decode step over the ``active`` slots; records the requests
        it finishes and returns the virtual clock after the step."""
        tr = self.trace
        t0 = time.perf_counter()
        toks = np.zeros((self.max_batch, 1), np.int32)
        for i in active:
            toks[i, 0] = self.slots[i].tokens[-1]
        with OFF if tr is None else tr.span("engine.decode.call"):
            nxt, _, self.cache = self._decode(self.params,
                                              jnp.asarray(toks), self.cache)
            nxt = np.array(jax.device_get(nxt))
        now += time.perf_counter() - t0

        for i in active:
            s = self.slots[i]
            s.tokens.append(int(nxt[i]))
            s.generated += 1
            if s.generated >= s.gen_len or s.kv_tokens >= self.max_len - 1:
                denom = max(s.generated - 1, 1)
                records[s.rid] = RequestResult(
                    rid=s.rid, arrival=s.arrival,
                    ttft=s.first_token_t - s.arrival,
                    tpot=(now - s.first_token_t) / denom,
                    e2e=now - s.arrival, tokens=list(s.tokens),
                    first_logits=s.first_logits)
                self.slots[i] = _Slot()
        # KV budget enforcement (greedy batching can overshoot)
        while self._kv_used() > self.kv_budget:
            self._evict_most_recent()
        with OFF if tr is None else tr.span("engine.decode.sync"):
            # the step advanced every slot; an idle one must not grow
            if not all(s.active for s in self.slots):
                self.cache["len"] = self._lengths()
                self._set_advance()
        return now

    def _set_advance(self, only: Optional[int] = None) -> None:
        """Upload which slots the next steps advance, for a cache that
        holds SSM state: slot ``only`` (a replay), else the active slots."""
        if "advance" not in self.cache:
            return
        adv = np.array([s.active for s in self.slots]) if only is None \
            else np.arange(self.max_batch) == only
        self.cache["advance"] = jnp.asarray(adv)

    def _lengths(self, replaying=None) -> jnp.ndarray:
        """Every slot's cache length as the host knows it, uploaded: an
        active slot holds its prompt and each generated token but the last
        (not yet fed), an idle one 0; ``replaying`` = (slot, tokens fed)
        for a slot whose prompt is being replayed."""
        lens = np.array([s.kv_tokens - 1 if s.active else 0
                         for s in self.slots], np.int32)
        if replaying is not None:
            lens[replaying[0]] = replaying[1]
        return jnp.asarray(lens)
