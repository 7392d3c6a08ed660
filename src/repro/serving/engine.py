"""Continuous-batching serving engine (real execution, any backend).

Implements iteration-level batching over a slot-based KV cache:

  * ``max_batch`` slots share one cache pytree; each slot holds one active
    request (its KV rows + length counter);
  * admission is greedy on free slots AND free KV-token budget — exactly
    the Batching Module's policy (core/batching.py), including preemption
    of the most-recently-admitted request when the token budget overflows;
  * each engine iteration runs ONE jitted decode step over all slots
    (inactive slots are masked); prefill populates a request's slot via the
    token-replay prefill;
  * arrivals are honored in VIRTUAL time: the clock advances by measured
    step wall-times, and a request joins the queue once the virtual clock
    passes its arrival stamp.  This makes CPU-scale fidelity runs directly
    comparable with the simulator's virtual-clock results (Fig. 6/7).

Checkpointable: ``snapshot()``/``restore()`` capture queued + in-flight
request state so a restarted replica replays its work (fault tolerance).
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

    @property
    def ttft_mean(self) -> float:
        return float(np.mean([r.ttft for r in self.results]))

    @property
    def tpot_mean(self) -> float:
        ts = [r.tpot for r in self.results if r.tpot > 0]
        return float(np.mean(ts)) if ts else 0.0

    @property
    def throughput(self) -> float:
        toks = sum(len(r.tokens) for r in self.results)
        return toks / self.total_time if self.total_time else 0.0


class ServingEngine:
    """Each ``RequestResult`` carries the request's first-token logits as
    served — what a correctness check compares against a reference forward
    pass."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 max_len: int = 512, kv_token_budget: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.kv_budget = kv_token_budget or (max_batch * max_len)
        self.slots = [_Slot() for _ in range(max_batch)]
        self.cache = T.init_cache(cfg, max_batch, max_len)
        self.queue: List[dict] = []
        self._order = 0
        self.preemptions = 0
        def _step(p, t, c):
            logits, c2 = T.decode_step(p, cfg, t, c)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, c2

        self._decode = jax.jit(_step)

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
            self._prefill_slot(i)
            now += time.perf_counter() - t0
            self.slots[i].first_token_t = now
        return now

    def _prefill_slot(self, i: int) -> None:
        """Replay the prompt through the jitted decode step (correctness-
        first prefill; the whole batch's other slots ride along masked)."""
        s = self.slots[i]
        lens = np.array(jax.device_get(self.cache["len"]))
        lens[i] = 0
        self.cache["len"] = jnp.asarray(lens)
        for t in range(len(s.prompt)):
            toks = np.zeros((self.max_batch, 1), np.int32)
            toks[i, 0] = s.prompt[t]
            logits_tok, logits, cache = self._decode(
                self.params, jnp.asarray(toks), self.cache)
            # only slot i's length may advance
            new_len = np.array(jax.device_get(cache["len"]))
            keep = np.array(jax.device_get(self.cache["len"]))
            keep[i] = new_len[i]
            cache["len"] = jnp.asarray(keep)
            self.cache = cache
        s.generated = 1
        first = int(jax.device_get(logits_tok)[i])
        s.tokens.append(first)
        s.first_logits = np.asarray(jax.device_get(logits[i]), np.float32)

    def _evict_most_recent(self) -> None:
        cand = [s for s in self.slots if s.active]
        if not cand:
            return
        victim = max(cand, key=lambda s: s.order)
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
        meta = {r["rid"]: dict(arrival=r["arrival"] * time_scale,
                               first=None, start=None) for r in requests}
        now = 0.0
        iters = 0
        while self.queue or any(s.active for s in self.slots):
            now = self._admit(now)
            active = [i for i, s in enumerate(self.slots) if s.active]
            if not active:
                if self.queue:
                    now = max(now, self.queue[0]["arrival"])
                    continue
                break

            t0 = time.perf_counter()
            toks = np.zeros((self.max_batch, 1), np.int32)
            for i in active:
                toks[i, 0] = self.slots[i].tokens[-1]
            nxt, _, cache = self._decode(self.params, jnp.asarray(toks),
                                         self.cache)
            nxt = np.array(jax.device_get(nxt))
            # inactive slots must not advance their length counters
            new_len = np.array(jax.device_get(cache["len"]))
            old_len = np.array(jax.device_get(self.cache["len"]))
            mask = np.zeros(self.max_batch, bool)
            mask[active] = True
            new_len = np.where(mask, new_len, old_len)
            cache["len"] = jnp.asarray(new_len)
            self.cache = cache
            now += time.perf_counter() - t0
            iters += 1

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

        return EngineReport(results=list(records.values()), total_time=now,
                            iterations=iters, preemptions=self.preemptions)
