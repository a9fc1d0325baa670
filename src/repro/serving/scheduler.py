"""Request schedulers: per-request accounting on top of the hybrid
engine (real-time framing of the paper: the detector doubles as a
traffic offloader — private requests never wait on the network path).

Two schedulers share the queue/Response protocol:
  * ``Scheduler`` — sequential reference path, one request at a time.
  * ``ContinuousBatchScheduler`` — packs requests into the
    ``BatchedHybridEngine`` decode lanes and refills freed rows as
    sequences hit EOS (continuous batching).

Latency semantics: ``Response.wall_seconds`` is measured from
``Request.submitted_at`` — it INCLUDES the time the request sat in the
queue waiting for a free lane slot (the latency the paper's real-time
claim is about), which is also broken out as
``Response.queue_wait_seconds``.  ``summarize`` reports queue-wait
mean/p95 alongside the per-token latencies.
"""
from __future__ import annotations

import enum
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from repro.serving.adapters import UnknownAdapter
from repro.serving.deployment import ServingDeployment
from repro.serving.engine import (BatchedHybridEngine, GenStats,
                                  HybridEngine)


class ResponseStatus(enum.Enum):
    """Consolidated request outcome — one enum instead of reading the
    ``error``/``truncated``/``cancelled`` flags separately.  Severity
    order when several apply: REJECTED > CANCELLED > TRUNCATED > OK
    (a hard reject never ran at all; a cancelled request served only
    partial text, which subsumes a clipped prompt)."""
    OK = "ok"
    TRUNCATED = "truncated"
    REJECTED = "rejected"
    CANCELLED = "cancelled"


@dataclass
class Request:
    rid: int
    prompt: str
    max_new_tokens: int = 16
    submitted_at: float = 0.0
    greedy: bool = True
    seed: Optional[int] = None       # sampling-key override (else rid)
    prefix: Optional[str] = None     # shared preamble (COW-shared paged)
    adapter_id: Optional[Any] = None  # per-user adapter (slot-cached)
    deadline_ms: Optional[float] = None  # simulated-clock decode budget


@dataclass
class Response:
    rid: int
    text: str
    stats: GenStats
    wall_seconds: float              # submit -> finish (incl. queue wait)
    queue_wait_seconds: float = 0.0  # submit -> admission into a lane
    error: Optional[str] = None      # hard admission reject (never ran)
    truncated: bool = False          # prompt clipped to fit a dense row
    cancelled: bool = False          # deadline hit; ``text`` is partial

    @property
    def status(self) -> ResponseStatus:
        if self.error is not None:
            return ResponseStatus.REJECTED
        if self.cancelled:
            return ResponseStatus.CANCELLED
        if self.truncated:
            return ResponseStatus.TRUNCATED
        return ResponseStatus.OK

    @property
    def degraded_tokens(self) -> int:
        """Tokens served SLM-only under a tripped circuit breaker."""
        return self.stats.degraded_tokens

    @property
    def cloud_lost(self) -> int:
        """Cloud attempts whose reply was injected-lost (loss/outage)."""
        return self.stats.cloud_lost


class Scheduler:
    """FIFO scheduler; private traffic is split from cloud-eligible
    traffic so a network stall never blocks on-device requests."""

    def __init__(self, engine: HybridEngine):
        self.engine = engine
        self.queue: List[Request] = []
        self._next = 0

    @classmethod
    def from_deployment(cls, deployment: ServingDeployment,
                        **engine_kw) -> "Scheduler":
        """Build the sequential engine through a ServingDeployment (the
        placement layer owns params/mesh/compiled entry points)."""
        return cls(HybridEngine(deployment=deployment, **engine_kw))

    def submit(self, prompt: str, max_new_tokens: int = 16,
               greedy: bool = True, seed: Optional[int] = None,
               prefix: Optional[str] = None,
               adapter_id: Optional[Any] = None,
               deadline_ms: Optional[float] = None) -> int:
        rid = self._next
        self._next += 1
        self.queue.append(Request(rid, prompt, max_new_tokens, time.time(),
                                  greedy, seed, prefix, adapter_id,
                                  deadline_ms))
        return rid

    def run(self) -> List[Response]:
        """Serve the queue one request at a time.  Structurally immune
        to the no-progress hang the batched loop's watchdog guards:
        every iteration fully retires exactly one request (generate
        is bounded by max_new_tokens / its deadline)."""
        private, public = [], []
        for r in self.queue:
            (private if self.engine.detector.detect(
                (r.prefix or "") + r.prompt) else public).append(r)
        self.queue = []
        out = []
        # private first: strictly on-device, immune to network state
        for r in private + public:
            t0 = time.time()
            try:
                text, stats = self.engine.generate(
                    (r.prefix or "") + r.prompt, r.max_new_tokens,
                    greedy=r.greedy, rid=r.rid, sample_key_id=r.seed,
                    adapter_id=r.adapter_id, deadline_ms=r.deadline_ms)
            except UnknownAdapter as e:
                # hard reject, same surface as the batched scheduler's
                # pop_rejected path: the request never ran
                out.append(Response(
                    r.rid, "", GenStats(),
                    wall_seconds=time.time() - r.submitted_at,
                    queue_wait_seconds=t0 - r.submitted_at,
                    error=str(e)))
                continue
            out.append(Response(r.rid, text, stats,
                                wall_seconds=time.time() - r.submitted_at,
                                queue_wait_seconds=t0 - r.submitted_at,
                                truncated=stats.truncated,
                                cancelled=stats.cancelled))
        return sorted(out, key=lambda x: x.rid)


class ContinuousBatchScheduler:
    """Continuous batching: cloud-eligible requests share a hybrid decode
    batch, private requests an SLM-only batch; freed batch rows are
    refilled from the queue as sequences finish.

    With the macro-step engine (``macro_k=K``) every boundary decodes K
    tokens per occupied row in ONE jitted, cache-donating dispatch and
    replays the returned per-step traces into request bookkeeping — so
    admission happens at K-token macro boundaries: a row that frees
    mid-macro idles (parked on device, writes dropped) until the next
    boundary.  That shifts wall-clock admission timing but never any
    request's tokens/stats (latency draws and sampling keys are
    counter-based on (rid, step), independent of when a row is
    admitted).  ``macro_k=0`` restores the per-token cadence.

    ADMISSION PIPELINING: ``run`` dispatches the in-flight macro-step
    first (``engine.dispatch_step()``, no host sync), THEN admits the
    next burst — tokenization, the packed B>1 prefill dispatch, and the
    row scatter all overlap the decode executing on device — and only
    then pays the boundary's single host sync (``engine.collect_step()``,
    the trace fetch).  Admitted rows were parked for the whole in-flight
    scan, so outputs are bit-identical to unpipelined admission; only
    wall-clock timing improves.  With ``macro_k=0`` the dispatch phase
    is empty and the loop degenerates to admit-then-step."""

    def __init__(self, engine: BatchedHybridEngine,
                 watchdog_iters: int = 5000):
        self.engine = engine
        self.queue: List[Request] = []
        self._next = 0
        # no-progress bound for run(): after this many consecutive
        # boundaries with no admission, no rejection and no completion,
        # the loop raises a diagnostic instead of hanging CI
        self.watchdog_iters = watchdog_iters

    @classmethod
    def from_deployment(cls, deployment: ServingDeployment,
                        **engine_kw) -> "ContinuousBatchScheduler":
        """Build the continuous-batching engine through a
        ServingDeployment — engines constructed this way share the
        deployment's placed params and compiled entry points."""
        return cls(BatchedHybridEngine(deployment=deployment, **engine_kw))

    def submit(self, prompt: str, max_new_tokens: int = 16,
               greedy: bool = True, seed: Optional[int] = None,
               prefix: Optional[str] = None,
               adapter_id: Optional[Any] = None,
               deadline_ms: Optional[float] = None) -> int:
        rid = self._next
        self._next += 1
        self.queue.append(Request(rid, prompt, max_new_tokens, time.time(),
                                  greedy, seed, prefix, adapter_id,
                                  deadline_ms))
        return rid

    def _wedge_diagnostics(self, pending: List[Request]) -> str:
        """Everything a post-mortem needs when the loop stops making
        progress: who is stuck waiting, lane/pool/adapter occupancy,
        and the fault/breaker health counters."""
        eng = self.engine
        lines = [
            f"pending rids: {[r.rid for r in pending]}",
            f"active rows: {eng.active_count()}",
        ]
        for name, lane in (("cloud", eng.cloud_lane),
                           ("edge", eng.edge_lane)):
            free = len(lane.free_slots())
            pools = []
            for pager in (lane.pager_s, lane.pager_l):
                if pager is not None:
                    pools.append(f"{pager.alloc.free_pages}"
                                 f"/{pager.alloc.num_pages}")
            lines.append(f"{name} lane: {free}/{lane.batch} slots free, "
                         f"evictq={len(lane._evictq)}, "
                         f"free pages={pools or 'dense'}")
        lines.append(f"growth: {eng.growth_stats()}")
        if eng.adapter_stats():
            lines.append(f"adapters: {eng.adapter_stats()}")
        lines.append(f"health: {eng.health_stats()}")
        return "; ".join(lines)

    def run(self) -> List[Response]:
        pending = list(self.queue)
        self.queue = []
        submitted_at = {r.rid: r.submitted_at for r in pending}
        admitted_at: Dict[int, float] = {}
        out: List[Response] = []
        stalled = 0
        while pending or self.engine.active_count():
            progressed = False
            # enqueue this boundary's macro-step(s) before any host-side
            # admission work — the trace fetch happens in collect_step,
            # so everything between here and there overlaps the decode
            self.engine.dispatch_step()
            # fill freed slots as ONE admission burst per macro boundary
            # (FIFO per lane: once a request is soft-refused, later
            # arrivals bound for the SAME lane are held back too, so a
            # big request can never be starved by a stream of small
            # later ones; a full lane skips, a later request bound
            # for the other lane may still be admitted) — all admissions
            # that land in a lane this step share a single packed B>1
            # prefill, dispatched while the macro-step is in flight
            if pending:
                flags = self.engine.add_requests(
                    [(r.prompt, r.max_new_tokens, r.greedy, r.rid, r.seed,
                      r.prefix, r.adapter_id, r.deadline_ms)
                     for r in pending])
                now = time.time()
                # hard rejects (paged: page demand beyond pool capacity)
                # error out instead of spinning in the pending queue
                rejected = dict(self.engine.pop_rejected()) \
                    if hasattr(self.engine, "pop_rejected") else {}
                still: List[Request] = []
                for r, ok in zip(pending, flags):
                    if ok:
                        admitted_at[r.rid] = now
                        progressed = True
                    elif r.rid in rejected:
                        out.append(Response(
                            r.rid, "", GenStats(),
                            wall_seconds=now - r.submitted_at,
                            queue_wait_seconds=now - r.submitted_at,
                            error=rejected[r.rid]))
                        progressed = True
                    else:
                        still.append(r)
                pending = still
            for rid, text, stats in self.engine.collect_step():
                now = time.time()
                out.append(Response(
                    rid, text, stats,
                    wall_seconds=now - submitted_at[rid],
                    queue_wait_seconds=(admitted_at[rid]
                                        - submitted_at[rid]),
                    truncated=stats.truncated,
                    cancelled=stats.cancelled))
                progressed = True
            # watchdog: a boundary that admits nothing, rejects nothing
            # and completes nothing is a stall.  A bounded run of them
            # is normal (rows decoding mid-request complete within
            # max_new/macro_k boundaries, far under the default bound);
            # an unbounded run means the engine is wedged — rows parked
            # forever, or pending requests that can never admit — so
            # raise the post-mortem instead of spinning CI forever.
            if progressed:
                stalled = 0
            else:
                stalled += 1
                if stalled >= self.watchdog_iters:
                    raise RuntimeError(
                        "ContinuousBatchScheduler wedged: "
                        f"{stalled} boundaries with no progress — "
                        + self._wedge_diagnostics(pending))
        return sorted(out, key=lambda x: x.rid)


def summarize(responses: List[Response]) -> Dict[str, float]:
    lat = [r.stats.mean_latency_ms for r in responses if r.stats.latency_ms]
    waits = [r.queue_wait_seconds for r in responses]
    drafted = sum(r.stats.spec_drafted for r in responses)
    accepted = sum(r.stats.spec_accepted for r in responses)
    return {
        "requests": len(responses),
        "private_frac": float(np.mean([r.stats.private for r in responses])),
        "cloud_token_frac": float(np.mean(
            [r.stats.cloud_tokens / max(1, r.stats.tokens)
             for r in responses])),
        "fallback_token_frac": float(np.mean(
            [r.stats.fallback_tokens / max(1, r.stats.tokens)
             for r in responses])),
        "mean_token_latency_ms": float(np.mean(lat)) if lat else 0.0,
        "p95_token_latency_ms": float(np.percentile(
            [x for r in responses for x in r.stats.latency_ms], 95))
        if lat else 0.0,
        "p99_token_latency_ms": float(np.percentile(
            [x for r in responses for x in r.stats.latency_ms], 99))
        if lat else 0.0,
        # cloud DISPATCHES per emitted token, distinct from the fused-
        # TOKEN fraction above: a speculative engine fuses (up to) k
        # tokens per LLM round-trip, so this drops below
        # cloud_token_frac exactly when speculation is paying off
        "cloud_calls_per_token": float(np.mean(
            [r.stats.cloud_calls / max(1, r.stats.tokens)
             for r in responses])),
        # speculative accept-rate over all responses (0.0 when the
        # engine never drafted)
        "accept_rate": float(accepted / max(1, drafted)),
        "degraded_token_frac": float(np.mean(
            [r.stats.degraded_tokens / max(1, r.stats.tokens)
             for r in responses])),
        "cancelled": int(sum(bool(r.cancelled) for r in responses)),
        "mean_queue_wait_s": float(np.mean(waits)) if waits else 0.0,
        "p95_queue_wait_s": float(np.percentile(waits, 95))
        if waits else 0.0,
    }
