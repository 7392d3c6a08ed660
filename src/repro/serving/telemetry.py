"""Spans the serving engine records at its layer boundaries.

``ServingEngine(..., trace=EngineTrace())`` records one ``Span`` each time
it crosses a boundary: the name, start and end on ``time.perf_counter()``,
the span that was open when it started (``parent``, an index into
``EngineTrace.spans``), the request it serves (``rid``; a span given none
takes its parent's, so the spans of one request share it) and ``attrs``,
the counts taken at that boundary.  Each span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
trace the same spans land in the host plane, on the device trace's clock.
With no recorder (``trace=None``, the default) a span site costs one
``is None`` check: nothing is allocated, annotated or synced.  The caller
owns the recorder and drops it when done.

The names and attrs are an interface.  A later change that rebuilds a
layer (a batched prefill, fewer host syncs) keeps emitting its span under
the same name with the same attrs, so that what reads them goes on
reading the same quantity.  The donated cache, which the step now writes
in place (``engine.make_decode_step``), kept every span name and attr.

=======================  ===================================================
``engine.prefill``       one request's prompt replay; ``tokens`` replayed,
                         ``waited_s`` (engine clock at admission minus the
                         request's arrival)
``engine.prefill.sync``  the replay's host syncs: the length reset, the
                         per-token length fix-up (an upload of the
                         lengths the host keeps) and wait on the step,
                         the first token and logits fetch; with SSM
                         state, the uploads of the slots the steps
                         advance at the replay's start and end
``engine.decode``        one decode iteration; ``active`` slots,
                         ``kv_tokens`` in use over all slots,
                         ``kv_reserved`` (``max_batch * max_len``),
                         ``state_reserved``: bytes of recurrent (SSM)
                         state and conv windows the cache holds for all
                         slots (0 without SSM layers)
``engine.decode.call``   the step call and its tokens' fetch: the host
                         waiting on the device
``engine.decode.sync``   the length fix-up: one upload of the lengths
                         the host keeps (and, with SSM state, of the
                         slots the next step advances), when a slot is
                         idle (a full batch needs none)
``engine.evict``         one preemption
=======================  ===================================================

Each span and attr has a reader: ``summary`` here, an operator's line.

Inside the jitted step, the MLA, MoE and SSM layers put their device ops
under ``jax.named_scope`` scopes.  The compiled step carries the scope in each
instruction's ``op_name`` metadata (``.../<scope>/...``); a profiler
trace names the instruction, so a device op's scope is read from the
compiled step.  They are an interface too, kept under these names by a
later rebuild of the layer:

=================  =========================================================
``mla.decode``     ``layers/attention.py`` ``mla_decode_step``: q and latent
                   projections, the latent and rope-key cache writes, the
                   expansion of the cached latents to per-head K/V, the
                   scores, the softmax and the readout through ``wo``
``moe.route``      ``layers/moe.py``: router logits, the gates and the
                   (B, S, E) combine weights
``moe.experts``    ``layers/moe.py``: the routed experts, weighted by their
                   gates
``moe.shared``     ``layers/moe.py``: the shared experts on every token
``ssm.decode``     ``layers/ssm.py`` ``mamba2_decode_step``: the x, z and
                   B/C/dt projections, the conv windows, the state update
                   and readout (each slot's state advanced or kept, and
                   zeroed where a replay starts), the gated norm and
                   ``w_out``
=================  =========================================================

No reader in the repository reads them yet; ``summary()`` does not.
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from typing import Dict, Iterator, List, Optional, Sequence

import jax

SPAN_NAMES = ("engine.prefill", "engine.prefill.sync", "engine.decode",
              "engine.decode.call", "engine.decode.sync", "engine.evict")

# What a span site enters when no recorder is given: stateless, so shared.
OFF = contextlib.nullcontext()


@dataclasses.dataclass
class Span:
    name: str
    start: float                   # time.perf_counter()
    end: float
    parent: Optional[int]          # index in EngineTrace.spans; None: a root
    rid: Optional[int]
    attrs: Dict[str, float]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class EngineTrace:
    """In-memory record of one engine's spans, in the order they opened."""

    def __init__(self):
        self.spans: List[Span] = []
        self._open: List[int] = []

    @contextlib.contextmanager
    def span(self, name: str, rid: Optional[int] = None,
             **attrs) -> Iterator[None]:
        parent = self._open[-1] if self._open else None
        if rid is None and parent is not None:
            rid = self.spans[parent].rid
        sp = Span(name, 0.0, 0.0, parent, rid, attrs)
        self._open.append(len(self.spans))
        self.spans.append(sp)
        with jax.profiler.TraceAnnotation(name):
            sp.start = time.perf_counter()
            try:
                yield
            finally:
                sp.end = time.perf_counter()
                self._open.pop()


# ---------------------------------------------------------------------------
# the operator's line
# ---------------------------------------------------------------------------

def _decode_host_gaps(spans: Sequence[Span]) -> List[float]:
    """Seconds from each ``engine.decode.call``'s end to the next one's
    start, over consecutive decode iterations with no prefill between: the
    time the device waits on the host in each step."""
    gaps, last = [], None
    for sp in sorted(spans, key=lambda s: s.start):
        if sp.name == "engine.prefill":
            last = None
        elif sp.name == "engine.decode.call":
            if last is not None:
                gaps.append(sp.start - last)
            last = sp.end
    return gaps


def _per_prefill_token(spans: Sequence[Span], name: str) -> Optional[float]:
    """Seconds in spans called ``name`` per prompt token replayed."""
    tokens = sum(s.attrs["tokens"] for s in spans
                 if s.name == "engine.prefill")
    return sum(s.seconds for s in spans if s.name == name) / tokens \
        if tokens else None


def _median(xs: Sequence[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None


def _seconds(spans: Sequence[Span], name: str) -> List[float]:
    return [s.seconds for s in spans if s.name == name]


def summary(spans: Sequence[Span]) -> str:
    """One line for an operator (host clock, milliseconds): decode
    iterations and their mean active slots; per step the median host time
    between step calls, the median wait on the device and the median length
    fix-up; mean KV in use over reserved; per replayed prompt token the
    replay and its host syncs; median admission wait; preemptions."""
    def ms(x):
        return "-" if x is None else f"{x * 1e3:.2f}"
    decodes = [s for s in spans if s.name == "engine.decode"]
    active = statistics.fmean(s.attrs["active"] for s in decodes) \
        if decodes else 0.0
    kv = statistics.fmean(s.attrs["kv_tokens"] / s.attrs["kv_reserved"]
                          for s in decodes) if decodes else None
    waits = [s.attrs["waited_s"] for s in spans if s.name == "engine.prefill"]
    return (f"{len(decodes)} decode iterations, {active:.1f} slots active: "
            f"host {ms(_median(_decode_host_gaps(spans)))} ms per step "
            f"against {ms(_median(_seconds(spans, 'engine.decode.call')))} "
            f"ms waiting on the device, length fix-up "
            f"{ms(_median(_seconds(spans, 'engine.decode.sync')))} ms, KV "
            f"in use {'-' if kv is None else f'{kv:.1%}'} of reserved; "
            f"prefill {ms(_per_prefill_token(spans, 'engine.prefill'))} ms "
            f"per token, of it host syncs "
            f"{ms(_per_prefill_token(spans, 'engine.prefill.sync'))} ms, "
            f"admission wait median {ms(_median(waits))} ms; "
            f"{sum(s.name == 'engine.evict' for s in spans)} preemptions "
            f"(host clock)")
