"""Hybrid LLM-SLM serving engine — the paper's inference phase end-to-end.

Pipeline per request (Fig. 8):
  1. Privacy detector (Alg. 2): sensitive -> SLM-only, never leaves device.
  2. Parameter-free MoE router (Eq. 8-11): gate weights ω over the LoRA
     expert bank for the SLM.
  3. Token loop: SLM (with merged LoRA experts) and cloud LLM decode in
     parallel; logits fused per Eq. 12-15; if the cloud misses the τ
     budget the fusion weight is forced to w=1 (Sec. IV-D fallback).

Placement is delegated wholesale to ``serving/deployment.py``: a
``ServingDeployment`` owns the mesh, the param + lane-cache shardings
and every compiled entry point; the engines here are host-side request
bookkeeping (slots, lanes, stats, admission) on top of it.  Engines can
be built either through an explicit ``deployment=`` (serve.py,
benchmarks — several engines may share one deployment and its compiled
programs) or from the legacy flat argument list, which constructs a
private deployment internally.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lora as LORA
from repro.core.privacy import PrivacyDetector
from repro.core.router import Router
from repro.data import tokenizer as TOK
from repro.models import attention as ATT
from repro.kernels.logit_fusion import ops as OPS
from repro.serving import latency as LAT
from repro.serving import paging as PAG
from repro.serving.deployment import ServingDeployment
from repro.serving.latency import LatencyModel

_BANK_NEEDS_GATING = (
    "expert_bank is set but nothing gates it — the bank would be "
    "silently dropped.  Pass router= to serve router-gated experts, or "
    "build the ServingDeployment with adapter_slots= and submit per-user "
    "requests with adapter_id=")


def _admission_gates(eng, items: List[Tuple[str, Optional[int]]],
                     bp: Optional[int] = None):
    """One (n, E) gate-row block per admission group — THE single gate
    constructor for every admission flavour (burst, B=1, packed paged,
    chunked).  ``items`` is [(prompt, adapter_slot)]; emits one-hot
    adapter-slot gates on an adapter-serving engine (slot None -> an
    all-zero row: with zero-filled empty slots the LoRA delta is an
    exact 0.0) or the legacy router softmax gates, zero-padded to ``bp``
    rows for packed prefills — the same np.stack + zero-pad discipline
    the four admission paths each hand-rolled, so the router path stays
    bit-for-bit.  None when the engine serves no LoRA at all."""
    if eng.adapters is not None:
        rows = LORA.slot_gates([a for _, a in items],
                               eng.adapters.num_slots)
    elif eng.router is not None and eng.bank is not None:
        rows = np.stack([np.asarray(eng.router.gate_weights(p))
                         for p, _ in items])
    else:
        return None
    if bp is not None:
        g = np.zeros((bp, rows.shape[1]), rows.dtype)
        g[:rows.shape[0]] = rows
        rows = g
    return jnp.asarray(rows)


def _reject_deployment_args(**named):
    """Engines given an explicit ``deployment=`` must not also receive
    deployment-level config — it would be silently ignored (the
    deployment already compiled with its own).  ``named`` maps arg name
    -> (value, default)."""
    clashing = [k for k, (v, d) in named.items() if v != d]
    if clashing:
        raise ValueError(
            "deployment-level arguments are ignored when deployment= is "
            f"given — set them on the ServingDeployment instead: "
            f"{sorted(clashing)}")


@dataclass
class GenStats:
    tokens: int = 0
    # the emitted token ids, in order (the response text is their byte
    # decode, which drops ids outside the byte range)
    token_ids: List[int] = field(default_factory=list)
    cloud_tokens: int = 0
    fallback_tokens: int = 0
    private: bool = False
    latency_ms: List[float] = field(default_factory=list)
    fusion_w: List[float] = field(default_factory=list)
    # the prompt was cut to fit the context budget — surfaced on the
    # Response instead of silently serving a shorter prompt
    truncated: bool = False
    # engine-wide admission sequence number (paged/batched paths):
    # observable FIFO order for the no-starvation regression tests
    admit_seq: int = -1
    # fault-injection telemetry: tokens decoded SLM-only because the
    # circuit breaker held the row degraded, and cloud attempts whose
    # reply was injected-lost (loss draw or outage window)
    degraded_tokens: int = 0
    cloud_lost: int = 0
    # cloud DISPATCHES, distinct from cloud-fused TOKENS: every LLM
    # round-trip the engine attempted for this request counts one,
    # whether or not the reply arrived in time (a timed-out attempt is
    # still a dispatch; a breaker-degraded token never dispatches).
    # Speculative decode emits up to k tokens per dispatch, so
    # cloud_calls < tokens is the tentpole's measurable win
    cloud_calls: int = 0
    # speculative decode telemetry: draft positions scored by the cloud
    # and the subset the fused distribution accepted (accept-rate =
    # spec_accepted / spec_drafted); zero on non-speculative engines
    spec_drafted: int = 0
    spec_accepted: int = 0
    # the request was cancelled at a decode boundary because its
    # simulated clock passed its deadline — the text is partial
    cancelled: bool = False
    # running simulated decode clock (sum of latency_ms) — what
    # deadlines compare against, maintained as tokens append
    clock_ms: float = 0.0

    @property
    def mean_latency_ms(self) -> float:
        return float(np.mean(self.latency_ms)) if self.latency_ms else 0.0

    def push_latency(self, lat_ms: float):
        self.latency_ms.append(lat_ms)
        self.clock_ms += lat_ms


class HybridEngine:
    """Floe inference engine pairing an edge SLM with a cloud LLM."""

    def __init__(self, slm=None, slm_params=None, llm=None, llm_params=None,
                 alignment_mlp=None, expert_bank=None,
                 router: Optional[Router] = None,
                 detector: Optional[PrivacyDetector] = None,
                 latency: Optional[LatencyModel] = None,
                 timeout_ms: float = 200.0, max_seq: int = 96,
                 sample_seed: int = 0,
                 deployment: Optional[ServingDeployment] = None):
        if deployment is None:
            deployment = ServingDeployment(
                slm, slm_params, llm, llm_params, alignment_mlp,
                expert_bank=expert_bank, latency=latency,
                timeout_ms=timeout_ms, max_seq=max_seq,
                sample_seed=sample_seed)
        else:
            _reject_deployment_args(
                slm=(slm, None), slm_params=(slm_params, None),
                llm=(llm, None), llm_params=(llm_params, None),
                alignment_mlp=(alignment_mlp, None),
                expert_bank=(expert_bank, None), latency=(latency, None),
                timeout_ms=(timeout_ms, 200.0), max_seq=(max_seq, 96),
                sample_seed=(sample_seed, 0))
        if deployment.llm is None or deployment.mlp is None:
            raise ValueError(
                "HybridEngine needs a hybrid deployment (llm + alignment "
                "mlp); an SLM-only deployment serves SoloEngine")
        self.dep = deployment
        self.slm, self.slm_params = deployment.slm, deployment.slm_params
        self.llm, self.llm_params = deployment.llm, deployment.llm_params
        self.mlp = deployment.mlp
        self.bank = deployment.bank
        self.router = router
        self.detector = detector or PrivacyDetector()
        self.latency = deployment.latency
        self.timeout_ms = deployment.timeout_ms
        self.max_seq = deployment.max_seq
        self.sample_seed = deployment.sample_seed
        # injected cloud-link faults (None = the fault-free oracle) and
        # the engine-wide degradation telemetry behind health_stats()
        self.fault = deployment.fault
        self._health = dict(losses=0, outage_steps=0, breaker_trips=0,
                            breaker_recoveries=0, degraded_tokens=0,
                            cancellations=0)
        # per-user adapter serving: the engine's OWN refcounted slot
        # cache over a fresh device bank (write_adapter_slot donates,
        # so caches never share buffers)
        self.adapters = (deployment.make_adapter_cache()
                         if deployment.adapter_slots else None)
        if self.bank is not None and router is None:
            raise ValueError(_BANK_NEEDS_GATING)
        if self.bank is not None and self.adapters is not None:
            raise ValueError(
                "router-gated expert bank and per-user adapter slots "
                "are mutually exclusive — one lane gates buffer cannot "
                "carry both semantics")
        # placed router-gated LoRA bank (legacy); adapter-serving
        # engines read the slot bank through the ``lora`` property
        self._lora = (deployment.lora
                      if router is not None and self.bank is not None
                      else None)

    @property
    def lora(self):
        """The LoRA tree the compiled entry points consume: the adapter
        cache's LIVE slot bank (re-read every dispatch — slot writes
        donate and replace the buffer), the placed router bank, or
        None.  Never hold this across a ``write_adapter_slot``."""
        if self.adapters is not None:
            return LORA.bank_for_model(self.adapters.bank)
        return self._lora

    def adapter_stats(self) -> Dict[str, int]:
        """Residency telemetry of the per-user adapter cache: hits,
        loads, evictions, refusals, plus resident/pinned slot counts.
        Empty on engines without adapter slots."""
        return self.adapters.stats() if self.adapters is not None else {}

    def health_stats(self) -> Dict[str, int]:
        """Fault/degradation telemetry: injected losses and outage
        steps seen by cloud attempts, circuit-breaker trips and
        recoveries, tokens served SLM-only under a tripped breaker, and
        deadline cancellations.  All zero on a fault-free engine."""
        return dict(self._health)

    def _fault_f32(self) -> Tuple[float, float]:
        """(edge, fallback) latencies in the float32 quantization the
        device fault path charges: degraded tokens cost the edge decode
        only, failed cloud attempts the full fallback wait."""
        edge = float(np.float32(self.latency.edge_compute_ms))
        return edge, max(edge, float(np.float32(self.timeout_ms)))

    def _mirror_breaker(self, slot: "_Slot", lost: bool, step: int):
        """Advance a slot's HOST breaker mirror by one attempted token
        and fold the outcome into the health counters.  The mirror runs
        the same ``breaker_step`` recurrence on the same weather the
        device carry integrates inside the macro scan, so it stays
        bit-equal to the device state at every boundary — the device
        state is authoritative DURING a scan, the mirror between scans
        (admission resets, eviction checkpoints, telemetry).

        Returns (degraded, raw_fail)."""
        fault = self.fault
        outage = fault.outage_at(step)
        raw = bool(lost) or outage
        (slot.bfails, slot.bcool, degraded, attempt, _fail, trip,
         recover) = LAT.breaker_step(slot.bfails, slot.bcool, True, raw,
                                     fault.breaker_n, fault.breaker_m)
        h = self._health
        if attempt:
            h["losses"] += int(bool(lost))
            h["outage_steps"] += int(outage)
        h["breaker_trips"] += int(trip)
        h["breaker_recoveries"] += int(recover)
        h["degraded_tokens"] += int(degraded)
        st = slot.stats
        st.degraded_tokens += int(degraded)
        st.cloud_lost += int(attempt and raw)
        return degraded, raw

    def _release_adapter(self, s: "_Slot"):
        """Drop a finished request's slot pin (EOS collect / forced
        completion).  Evicted-but-unfinished rows KEEP their pin — the
        slot must survive until their deterministic resume."""
        if self.adapters is not None and s.aslot is not None:
            self.adapters.release(s.aslot)

    def _sample_key(self, rid: Optional[int]):
        """Per-request PRNG root; fold_in(step) yields per-token keys, so
        no two requests (or tokens) ever share a sampling key."""
        return jax.random.fold_in(jax.random.key(self.sample_seed),
                                  0 if rid is None else rid)

    # ------------------------------------------------------------- public
    def generate(self, prompt: str, max_new_tokens: int = 16,
                 greedy: bool = True, rid: Optional[int] = None,
                 sample_key_id: Optional[int] = None,
                 adapter_id: Optional[Any] = None,
                 deadline_ms: Optional[float] = None
                 ) -> Tuple[str, GenStats]:
        """rid, when given, keys both the latency draws and the sampling
        PRNG per (request, token) — order-independent, so batched and
        sequential serving see identical network weather and samples.
        ``sample_key_id`` (a caller-supplied per-request seed, plumbed
        from ``Scheduler.submit``) overrides rid in the sampling key
        derivation only — latency draws stay keyed by rid.
        ``adapter_id`` pins a registered per-user adapter for the whole
        request (the solo reference the batched per-row path must match
        bit for bit); unknown ids raise ``adapters.UnknownAdapter``.
        ``deadline_ms`` bounds the simulated decode clock: token t is
        emitted iff the clock after token t-1 is still under it, then
        the request is cancelled with the partial text — the same rule
        the batched engine applies at its decode boundaries.  Fault
        weather (deployment ``fault=``) rides the rid-keyed path only:
        the rid-less legacy stream has no counter to key it."""
        dep = self.dep
        stats = GenStats()
        stats.private = self.detector.detect(prompt)
        gates = None
        lora = None
        aslot = None
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "adapter_id= needs a deployment built with "
                    "adapter_slots=")
            aslot = self.adapters.acquire(adapter_id)
            if aslot is None:       # pragma: no cover (B=1 releases)
                raise RuntimeError("no adapter slot free")
            gates = jnp.asarray(
                LORA.slot_gates([aslot], self.adapters.num_slots))
            lora = self.lora
        elif self.router is not None and self.bank is not None:
            gates = jnp.asarray(self.router.gate_weights(prompt))[None, :]
            lora = self.lora
        sample_key = self._sample_key(
            rid if sample_key_id is None else sample_key_id)

        raw = TOK.encode(prompt + " ")
        cap = self.max_seq - max_new_tokens - 1
        stats.truncated = len(raw) > cap
        ids = raw[:cap]
        toks = jnp.asarray([ids], jnp.int32)
        s_logits, s_cache = dep.slm_prefill(self.slm_params, toks,
                                            lora, gates)
        use_cloud = not stats.private
        if use_cloud:
            l_logits, l_cache = dep.llm_prefill(self.llm_params, toks)

        out_ids = stats.token_ids
        sl, ll = s_logits[:, 0], (l_logits[:, 0] if use_cloud else None)
        lat_row = ok_row = None
        if use_cloud and rid is not None:
            # a whole request's network weather in ONE vectorized
            # dispatch — the per-token scalar shim paid a jit dispatch
            # + blocking sync per decoded token
            lat_d, ok_d = dep.lat_request(
                jnp.int32(rid), jnp.arange(max_new_tokens,
                                           dtype=jnp.int32))
            lat_row, ok_row = np.asarray(lat_d), np.asarray(ok_d)
        lost_row = None
        if use_cloud and rid is not None and self.fault is not None:
            lost_d, _out_d = dep.fault_request(
                jnp.int32(rid), jnp.arange(max_new_tokens,
                                           dtype=jnp.int32))
            lost_row = np.asarray(lost_d)
        slot = _Slot(rid or 0, max_new_tokens, greedy, stats)
        edge32, fb32 = self._fault_f32()
        for _ in range(max_new_tokens):
            if deadline_ms is not None and stats.clock_ms >= deadline_ms:
                stats.cancelled = True
                self._health["cancellations"] += 1
                break
            if use_cloud:
                if lat_row is not None:
                    lat_ms, arrived = (float(lat_row[len(out_ids)]),
                                       bool(ok_row[len(out_ids)]))
                else:        # rid-less legacy path: stateful host stream
                    lat_ms, arrived = self.latency.token_latency_ms(
                        self.timeout_ms, rid=rid, step=len(out_ids))
                degraded = False
                if lost_row is not None:
                    degraded, raw = self._mirror_breaker(
                        slot, bool(lost_row[len(out_ids)]), len(out_ids))
                    if degraded:
                        lat_ms, arrived = edge32, False
                    elif raw:
                        lat_ms, arrived = fb32, False
                p_out, w = dep.fuse(dep.mlp, sl, ll, jnp.asarray(arrived))
                stats.cloud_tokens += int(arrived)
                stats.fallback_tokens += int(not arrived)
                # one LLM round-trip per token on this path — degraded
                # tokens are the only ones that never dispatch
                stats.cloud_calls += int(not degraded)
            else:
                lat_ms, arrived = self.latency.edge_compute_ms, False
                p_out = jax.nn.softmax(sl.astype(jnp.float32), -1)
                w = jnp.ones((1,))
            stats.push_latency(float(lat_ms))
            stats.fusion_w.append(float(w[0]))

            nxt = int(jnp.argmax(p_out[0])) if greedy else int(
                jax.random.categorical(
                    jax.random.fold_in(sample_key, len(out_ids)),
                    jnp.log(jnp.clip(p_out[0], 1e-9))))
            out_ids.append(nxt)
            stats.tokens += 1
            if nxt == TOK.EOS:
                break
            t = jnp.asarray([[nxt]], jnp.int32)
            s_logits, s_cache = dep.slm_decode(self.slm_params, s_cache, t,
                                               lora, gates)
            sl = s_logits[:, 0]
            if use_cloud:
                l_logits, l_cache = dep.llm_decode(self.llm_params,
                                                   l_cache, t)
                ll = l_logits[:, 0]
        if aslot is not None:
            self.adapters.release(aslot)
        return TOK.decode(out_ids), stats


# ===========================================================================
# Batched continuous decode
# ===========================================================================


@dataclass
class _Slot:
    """Host-side bookkeeping for one occupied decode-batch row."""
    rid: int
    max_new: int
    greedy: bool
    stats: GenStats
    key_id: Optional[int] = None     # per-request sampling seed override
    seq: int = -1                    # admission order (FIFO observable)
    # lazy-growth bookkeeping (paged lanes): the ORIGINAL prompt length
    # (write position of token n is always prompt_len + n, eviction and
    # resume included), the prompt ids for eviction re-prefill, and the
    # park flag (pos = FREED_POS on device, pending logits preserved)
    prompt_len: int = 0
    prompt_ids: List[int] = field(default_factory=list)
    full_text: str = ""
    parked: bool = False
    # per-user adapter: the pinned slot in the engine's AdapterCache
    # (released at completion, NOT at eviction — a parked request's
    # adapter must stay resident for its bit-identical resume)
    aslot: Optional[int] = None
    # circuit-breaker HOST MIRROR of the device carry (consecutive
    # injected failures, remaining degraded steps) — replayed from the
    # macro traces with the same recurrence, so it equals the device
    # state at every boundary and survives eviction/resume
    bfails: int = 0
    bcool: int = 0
    # simulated-clock deadline; None = no deadline
    deadline_ms: Optional[float] = None
    # speculative lanes: an eviction-resumed row's LLM cache came back
    # at FULL depth p (re-prefill of prompt + tokens-so-far) and must
    # be rewound to the one-behind protocol depth p-1 with the last
    # emitted token re-pended in ``lt`` before its next burst
    needs_spec_init: bool = False

    @property
    def out_ids(self) -> List[int]:
        return self.stats.token_ids


@dataclass
class _PagedJob:
    """One paged admission: tokenization and page reservation happen at
    ``add_requests`` time (the admission gate needs the page demand), so
    the job carries them to the lane's prefill + scatter."""
    slot: int
    prompt: str                      # FULL text (prefix + user prompt)
    max_new: int
    greedy: bool
    rid: int
    private: bool
    key_id: Optional[int]
    ids: List[int]                   # full token ids (already truncated)
    rows_s: Any                      # RowPages in the lane's SLM pager
    rows_l: Any                      # RowPages in the LLM pager (cloud)
    entry: Any                       # shared-prefix registry entry or None
    seq: int = -1                    # admission order
    truncated: bool = False
    resume: Any = None               # evicted _Slot to restore, or None
    aslot: Optional[int] = None      # pinned adapter slot, or None
    deadline_ms: Optional[float] = None


class _Lane:
    """One decode batch: stacked SLM (+ optionally LLM) caches with a
    free-slot list.  The cloud lane fuses SLM+LLM logits per row; the
    edge lane is SLM-only (private traffic, Alg. 2 split)."""

    def __init__(self, engine: "BatchedHybridEngine", batch: int,
                 use_cloud: bool):
        self.eng = engine
        self.batch = batch
        self.use_cloud = use_cloud
        self.slots: List[Optional[_Slot]] = [None] * batch
        self.s_cache = None          # allocated lazily on first admit
        self.l_cache = None
        self.sl = None               # (B, V) current SLM logits
        self.ll = None               # (B, V) current LLM logits
        # speculative lanes only: the (B,) last emitted token per row,
        # pending as the LLM's next feed (the one-behind protocol's
        # device carry — never synced to host between bursts)
        self.lt = None
        self.gates = None            # (B, E) router weights or None
        self._inflight = None        # dispatched macro awaiting replay
        # paged lanes: host-side page bookkeeping per model + the COW
        # shared-prefix registry (prefix str -> entry dict, or None for
        # structurally unshareable prefixes)
        self.pager_s = self.pager_l = None
        self._prefixes: Dict[str, Any] = {}
        # lazy growth: requests evicted while parked, awaiting internal
        # re-admission (oldest first), and forced completions surfaced
        # at the next collect
        self._evictq: List[_Slot] = []
        self._pending_done: List[Tuple[int, str, GenStats]] = []
        # speculative lane: the LLM runs ONE BEHIND the SLM (depth p-1
        # with the last emitted token pending in ``lt``), so position
        # bookkeeping that unparks rows must restore the offset depth
        self._spec = use_cloud and bool(getattr(engine, "spec_k", 0))
        if getattr(engine, "paged", False):
            self.pager_s = engine._make_pager(engine.dep.slm, batch)
            if use_cloud:
                self.pager_l = engine._make_pager(engine.dep.llm, batch)

    # ----------------------------------------------------------- helpers
    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _decode_gates(self):
        """The gates argument for DECODE dispatches: normally the dense
        (B, E) one-hot buffer; with ``use_slot_kernel`` on an adapter-
        serving engine, the (B,) int32 per-row adapter slots (-1 =
        adapter-free) instead — ``layers.lora_delta`` routes integer
        1-D gates through the scalar-prefetch ``moe_lora_delta_slots``
        kernel, gathering exactly one expert per row instead of the
        dense Σ over E.  Prefill always keeps the one-hot path (cold,
        and the packed batch amortizes the dense sweep); router-gated
        engines keep it too (their gates are soft weights, which the
        engine constructor keeps mutually exclusive with adapters)."""
        eng = self.eng
        if not getattr(eng, "use_slot_kernel", False) \
                or eng.adapters is None or self.gates is None:
            return self.gates
        slots = np.full((self.batch,), -1, np.int32)
        for i, s in enumerate(self.slots):
            if s is not None and s.aslot is not None:
                slots[i] = s.aslot
        return jnp.asarray(slots)

    def _alloc(self, vocab: int, n_experts: Optional[int]):
        dep = self.eng.dep
        b = self.batch
        if n_experts is None and self.eng.adapters is not None:
            # adapter-serving lanes always carry a gates buffer: the
            # first admission may be adapter-free (zero rows) but later
            # rows scatter their one-hot slot gates into it
            n_experts = self.eng.adapters.num_slots

        def pool_pages(pager):
            lp = (pager.local_alloc.num_pages
                  if pager.local_alloc is not None else 0)
            return pager.alloc.num_pages, lp

        if self.pager_s is not None:
            self.s_cache = dep.init_paged_lane_cache(
                dep.slm, b, *pool_pages(self.pager_s))
        else:
            self.s_cache = dep.init_lane_cache(dep.slm, b)
        if self.use_cloud:
            if self.pager_l is not None:
                self.l_cache = dep.init_paged_lane_cache(
                    dep.llm, b, *pool_pages(self.pager_l))
            else:
                self.l_cache = dep.init_lane_cache(dep.llm, b)
            self.ll = dep.commit_replicated(
                jnp.zeros((b, vocab), jnp.float32))
            self.lt = dep.commit_replicated(jnp.zeros((b,), jnp.int32))
        self.sl = dep.commit_replicated(jnp.zeros((b, vocab), jnp.float32))
        if n_experts is not None:
            self.gates = dep.commit_replicated(
                jnp.zeros((b, n_experts), jnp.float32))

    # --------------------------------------------------------- admission
    def admit_many(self, jobs: List[Tuple]):
        """Admit a burst of requests in ONE packed B>1 prefill.

        jobs: [(slot, prompt, max_new, greedy, rid, private, key_id,
        aslot, deadline_ms)].
        Prompts are right-padded to a shared chunk-rounded length and prefilled
        as a single jitted call with per-row valid lengths masked
        (``LM.prefill_packed``); the batch axis is padded to a power of
        two so retraces stay bounded.  Each resulting cache row is then
        scattered into its free lane slot.

        Safe to call while a macro-step is in flight (the pipelined
        scheduler does): target slots are by construction parked rows
        of the running scan, and the scatter is dispatched against the
        macro's OUTPUT caches."""
        eng = self.eng
        dep = eng.dep
        if not jobs:
            return
        if eng.paged:
            self._admit_paged(jobs)
            return
        if not eng.packed_prefill:
            for j in jobs:
                self._admit_one(*j)
            return
        n = len(jobs)
        raw = [TOK.encode(p + " ") for _, p, *_ in jobs]
        caps = [eng.max_seq - mn - 1 for _, _, mn, *_ in jobs]
        trunc = [len(r) > c for r, c in zip(raw, caps)]
        ids = [r[:c] for r, c in zip(raw, caps)]
        lens = np.asarray([len(seq) for seq in ids], np.int32)
        chunk = eng.prefill_chunk
        lpad = min(-(-int(lens.max()) // chunk) * chunk, eng.max_seq)
        bp = 1 << (n - 1).bit_length()
        toks = np.zeros((bp, lpad), np.int32)
        for j, seq in enumerate(ids):
            toks[j, :len(seq)] = seq
        lens_p = np.ones((bp,), np.int32)      # pad rows: length-1 dummies
        lens_p[:n] = lens
        g = _admission_gates(eng, [(j[1], j[7]) for j in jobs], bp=bp)
        toks_j, lens_j = jnp.asarray(toks), jnp.asarray(lens_p)
        s_logits, s_cache = dep.slm_prefill_packed(
            eng.slm_params, toks_j, lens_j, eng.lora, g)
        if self.s_cache is None:
            self._alloc(s_logits.shape[-1],
                        None if g is None else g.shape[-1])
        l_logits = l_cache = None
        if self.use_cloud:
            l_logits, l_cache = dep.llm_prefill_packed(
                eng.llm_params, toks_j, lens_j)
        src = jnp.arange(n)
        dst = jnp.asarray([j[0] for j in jobs], jnp.int32)
        self.s_cache = dep.insert_slm(self.s_cache, s_cache, src, dst)
        self.sl = dep.insert_row(self.sl, s_logits[:, 0], src, dst)
        if self.use_cloud:
            self.l_cache = dep.insert_llm(self.l_cache, l_cache, src, dst)
            self.ll = dep.insert_row(self.ll, l_logits[:, 0], src, dst)
        if g is not None:
            self.gates = dep.insert_row(self.gates, g, src, dst)
        for jdx, (slot, prompt, max_new, greedy, rid, private,
                  key_id, aslot, deadline) in enumerate(jobs):
            seq = eng._next_seq()
            st = GenStats(private=private, truncated=trunc[jdx],
                          admit_seq=seq)
            self.slots[slot] = _Slot(rid, max_new, greedy, st,
                                     key_id=key_id, seq=seq,
                                     prompt_len=len(ids[jdx]),
                                     aslot=aslot, deadline_ms=deadline)

    def _admit_one(self, slot: int, prompt: str, max_new: int,
                   greedy: bool, rid: int, private: bool,
                   key_id: Optional[int] = None,
                   aslot: Optional[int] = None,
                   deadline_ms: Optional[float] = None):
        """Legacy per-request B=1 prefill (kept as the burst-admission
        benchmark baseline and a bit-exact reference path)."""
        eng = self.eng
        dep = eng.dep
        gates_row = _admission_gates(eng, [(prompt, aslot)])
        raw = TOK.encode(prompt + " ")
        cap = eng.max_seq - max_new - 1
        ids = raw[:cap]
        toks = jnp.asarray([ids], jnp.int32)
        s_logits, s_cache = dep.slm_prefill(eng.slm_params, toks,
                                            eng.lora, gates_row)
        if self.s_cache is None:
            self._alloc(s_logits.shape[-1],
                        None if gates_row is None else gates_row.shape[-1])
        src, dst = jnp.zeros((1,), jnp.int32), jnp.asarray([slot], jnp.int32)
        self.s_cache = dep.insert_slm(self.s_cache, s_cache, src, dst)
        self.sl = dep.insert_row(self.sl, s_logits[:, 0], src, dst)
        if self.use_cloud:
            l_logits, l_cache = dep.llm_prefill(eng.llm_params, toks)
            self.l_cache = dep.insert_llm(self.l_cache, l_cache, src, dst)
            self.ll = dep.insert_row(self.ll, l_logits[:, 0], src, dst)
        if gates_row is not None:
            self.gates = dep.insert_row(self.gates, gates_row, src, dst)
        seq = eng._next_seq()
        self.slots[slot] = _Slot(rid, max_new, greedy,
                                 GenStats(private=private,
                                          truncated=len(raw) > cap,
                                          admit_seq=seq),
                                 key_id=key_id, seq=seq,
                                 prompt_len=len(ids), aslot=aslot,
                                 deadline_ms=deadline_ms)

    # ----------------------------------------------------- paged admission
    def ensure_prefix(self, prefix: str):
        """The lane's COW registry entry for ``prefix`` — built lazily,
        and the expensive part (B=1 preamble prefill + pool page write)
        runs exactly ONCE per (lane, prefix): later admissions only fork
        the shared page ids into their block tables.

        Returns None when the prefix is structurally unshareable (under
        one page — cached) or when the pools can't currently hold its
        pages (not cached; retried on a later admission)."""
        eng = self.eng
        dep = eng.dep
        if prefix in self._prefixes:
            return self._prefixes[prefix]
        ps = dep.page_size
        pre_ids = TOK.encode(prefix)
        share_np = len(pre_ids) // ps       # whole pages only (COW unit)
        # structurally unshareable: under one page, or no room left in
        # the context for any suffix + decode (admission truncates ids
        # to max_seq - max_new - 1, so such a prefix can never pass the
        # prefix-boundary compat check — allocating its pages here
        # would just leak them into the registry)
        if share_np == 0 or len(pre_ids) >= eng.max_seq - 2:
            self._prefixes[prefix] = None
            return None
        share_len = share_np * ps
        if self.s_cache is None:
            self._alloc(eng.slm.cfg.vocab_size, None)
        pids_s = self.pager_s.alloc.alloc(share_np)
        if pids_s is None:
            return None
        pids_l = None
        if self.use_cloud:
            pids_l = self.pager_l.alloc.alloc(share_np)
            if pids_l is None:
                self.pager_s.alloc.release(pids_s)
                return None
        toks = jnp.asarray([pre_ids], jnp.int32)
        # shared preambles are LoRA-free by construction (the COW gate
        # requires router is None and adapter_id is None), so never pass
        # a bank here: with gates=None, lora_delta would apply an
        # UNGATED sum over every slot
        hist_s = dep.slm_build_prefix(eng.slm_params, toks, None, None)
        content = eng.slm.prefix_page_rows(hist_s, share_len, ps,
                                           eng.max_seq)
        self.s_cache = dep.insert_slm_prefix(
            self.s_cache, content, jnp.asarray(pids_s, jnp.int32))
        hist_l = None
        if self.use_cloud:
            hist_l = dep.llm_build_prefix(eng.llm_params, toks)
            content_l = eng.llm.prefix_page_rows(hist_l, share_len, ps,
                                                 eng.max_seq)
            self.l_cache = dep.insert_llm_prefix(
                self.l_cache, content_l, jnp.asarray(pids_l, jnp.int32))
        entry = dict(pre_ids=list(pre_ids), pre_len=len(pre_ids),
                     share_np=share_np, share_len=share_len,
                     hist_s=hist_s, hist_l=hist_l,
                     pids_s=pids_s, pids_l=pids_l)
        self._prefixes[prefix] = entry
        return entry

    def _admit_paged(self, jobs: List[_PagedJob]):
        """Route a paged admission burst: long prompts (beyond the
        ``chunk_width`` dense prefill buffer) stream individually
        through chunked prefill; jobs sharing a prefix entry go through
        ONE suffix prefill over the shared history; the rest share one
        packed full prefill.  ``packed_prefill=False`` keeps the
        one-prefill-per-request cadence for benchmarks."""
        eng = self.eng
        wide = [j for j in jobs if len(j.ids) > eng.chunk_width]
        jobs = [j for j in jobs if len(j.ids) <= eng.chunk_width]
        if not self.eng.packed_prefill:
            groups = [[j] for j in jobs]
        else:
            by_key: Dict[Any, List[_PagedJob]] = {}
            for j in jobs:
                key = None if j.entry is None else id(j.entry)
                by_key.setdefault(key, []).append(j)
            groups = list(by_key.values())
        for group in groups:
            if group[0].entry is None:
                self._admit_paged_full(group)
            else:
                self._admit_paged_suffix(group, group[0].entry)
        for j in wide:
            self._admit_paged_chunked(j)

    def _finish_admit(self, j: _PagedJob):
        """Install the slot bookkeeping for an admitted paged job —
        fresh, or the preserved ``_Slot`` of an evicted request (its
        stats/out_ids/counters continue; the re-prefill of prompt +
        tokens-so-far landed it on exactly the distribution it was
        parked on)."""
        if j.resume is not None:
            s = j.resume
            s.parked = False
            if self.use_cloud and getattr(self.eng, "spec_k", 0):
                # the resume re-prefill landed the LLM at full depth;
                # _spec_seed rewinds it to the one-behind protocol
                s.needs_spec_init = True
            self.slots[j.slot] = s
            return
        s = _Slot(j.rid, j.max_new, j.greedy,
                  GenStats(private=j.private, truncated=j.truncated,
                           admit_seq=j.seq),
                  key_id=j.key_id, seq=j.seq,
                  prompt_len=len(j.ids), prompt_ids=list(j.ids),
                  full_text=j.prompt, aslot=j.aslot,
                  deadline_ms=j.deadline_ms)
        self.slots[j.slot] = s

    def _pad_group(self, ids: List[List[int]], width_cap: int):
        """Shared right-padding for an admission group: chunk-rounded
        length (bounded retraces), power-of-two batch, dummy pad rows of
        length 1 — the same padding discipline as the dense packed
        prefill, so paged admission stays bit-identical to it."""
        eng = self.eng
        n = len(ids)
        lens = np.asarray([len(seq) for seq in ids], np.int32)
        chunk = eng.prefill_chunk
        lpad = min(-(-int(lens.max()) // chunk) * chunk, width_cap)
        bp = 1 << (n - 1).bit_length()
        toks = np.zeros((bp, lpad), np.int32)
        for j, seq in enumerate(ids):
            toks[j, :len(seq)] = seq
        lens_p = np.ones((bp,), np.int32)
        lens_p[:n] = lens
        return jnp.asarray(toks), jnp.asarray(lens_p)

    def _paged_tables(self, jobs: List[_PagedJob], pager, rows_of):
        """(dpf, dpl, block, local) host arrays for an admission group:
        full block-table rows double as the destination-page rows for a
        full prefill (content pages line up with the table)."""
        block = np.stack([np.asarray(pager.table_row(rows_of(j)))
                          for j in jobs])
        if pager.nl:
            local = np.stack([np.asarray(pager.local_row(rows_of(j)))
                              for j in jobs])
        else:
            local = np.zeros((len(jobs), 0), np.int32)
        return (jnp.asarray(block), jnp.asarray(local))

    def _admit_paged_full(self, jobs: List[_PagedJob]):
        """Unshared paged admission: the DENSE packed prefill stays the
        source of truth (bit-identity with the dense oracle), reshaped
        to page rows and scattered into the pools at the reserved page
        ids."""
        eng = self.eng
        dep = eng.dep
        n = len(jobs)
        toks_j, lens_j = self._pad_group([j.ids for j in jobs],
                                         eng.max_seq)
        g = _admission_gates(eng, [(j.prompt, j.aslot) for j in jobs],
                             bp=int(toks_j.shape[0]))
        s_logits, s_cache = dep.slm_prefill_packed(
            eng.slm_params, toks_j, lens_j, eng.lora, g)
        if self.s_cache is None:
            self._alloc(s_logits.shape[-1],
                        None if g is None else g.shape[-1])
        src = jnp.arange(n)
        dst = jnp.asarray([j.slot for j in jobs], jnp.int32)
        rows_s = dep.slm_page_rows(s_cache)
        block, local = self._paged_tables(jobs, self.pager_s,
                                          lambda j: j.rows_s)
        self.s_cache = dep.insert_slm_paged(
            self.s_cache, rows_s, src, dst, block, local, block, local)
        self.sl = dep.insert_row(self.sl, s_logits[:, 0], src, dst)
        if self.use_cloud:
            l_logits, l_cache = dep.llm_prefill_packed(
                eng.llm_params, toks_j, lens_j)
            rows_l = dep.llm_page_rows(l_cache)
            blk_l, loc_l = self._paged_tables(jobs, self.pager_l,
                                              lambda j: j.rows_l)
            self.l_cache = dep.insert_llm_paged(
                self.l_cache, rows_l, src, dst, blk_l, loc_l, blk_l,
                loc_l)
            self.ll = dep.insert_row(self.ll, l_logits[:, 0], src, dst)
        if g is not None:
            self.gates = dep.insert_row(self.gates, g, src, dst)
        for j in jobs:
            self._finish_admit(j)

    def _admit_paged_suffix(self, jobs: List[_PagedJob], entry):
        """COW admission against a registered prefix: ONE packed suffix
        prefill over the shared history (the preamble itself is never
        recomputed), private page content scattered at each row's owned
        page ids, shared pages only block-mapped."""
        eng = self.eng
        dep = eng.dep
        ps = dep.page_size
        n = len(jobs)
        pre_len, share_len = entry["pre_len"], entry["share_len"]
        toks_j, lens_j = self._pad_group(
            [j.ids[pre_len:] for j in jobs], eng.max_seq - pre_len)
        # suffix (COW) admissions are LoRA-free by construction: the
        # sharing gate requires router is None AND adapter_id is None,
        # so pass no bank (gates=None + a bank would un-gate it)
        s_logits, rows_s = dep.slm_prefill_suffix(
            eng.slm_params, toks_j, lens_j, entry["hist_s"], None,
            None, pre_len, share_len)
        if self.s_cache is None:          # pragma: no cover (ensure_prefix)
            self._alloc(s_logits.shape[-1], None)
        src = jnp.arange(n)
        dst = jnp.asarray([j.slot for j in jobs], jnp.int32)
        np_content = PAG.pages_for(pre_len - share_len + toks_j.shape[1],
                                   ps)

        def owned_pages(pager, rows_of):
            dpf = np.full((n, np_content), PAG.NO_PAGE, np.int32)
            for i, j in enumerate(jobs):
                own = rows_of(j).owned
                m = min(len(own), np_content)
                dpf[i, :m] = own[:m]
            return jnp.asarray(dpf)

        dpf = owned_pages(self.pager_s, lambda j: j.rows_s)
        block, local = self._paged_tables(jobs, self.pager_s,
                                          lambda j: j.rows_s)
        self.s_cache = dep.insert_slm_paged(
            self.s_cache, rows_s, src, dst, dpf, local, block, local)
        self.sl = dep.insert_row(self.sl, s_logits[:, 0], src, dst)
        if self.use_cloud:
            l_logits, rows_l = dep.llm_prefill_suffix(
                eng.llm_params, toks_j, lens_j, entry["hist_l"],
                pre_len, share_len)
            dpf_l = owned_pages(self.pager_l, lambda j: j.rows_l)
            blk_l, loc_l = self._paged_tables(jobs, self.pager_l,
                                              lambda j: j.rows_l)
            self.l_cache = dep.insert_llm_paged(
                self.l_cache, rows_l, src, dst, dpf_l, loc_l, blk_l,
                loc_l)
            self.ll = dep.insert_row(self.ll, l_logits[:, 0], src, dst)
        for j in jobs:
            self._finish_admit(j)

    def _admit_paged_chunked(self, j: _PagedJob):
        """Long-prompt admission: stream the prompt page-chunk by
        page-chunk through the bounded dense prefill buffer (width
        ``chunk_width`` <= max_seq), freezing each chunk's KV into the
        row's reserved pool pages as it goes — prompts beyond the dense
        row width become servable.  Chunk 0 is a B=1 ``build_prefix``
        whose whole pages freeze like a COW prefix; every MIDDLE chunk
        is exactly chunk_width tokens (positions stay contiguous) and
        suffix-prefills against the history so far, extending it; the
        final ragged chunk also writes the ring/local window + row pos,
        and its last-token logits seed decode.  Each chunk's queries
        attend [history; fresh] at absolute positions, which causality
        makes bitwise the computation a one-shot prefill would run at
        those positions."""
        eng = self.eng
        dep = eng.dep
        ps = dep.page_size
        W = eng.chunk_width
        ids = j.ids
        gates_row = _admission_gates(eng, [(j.prompt, j.aslot)])
        # gates_row None means the engine serves no LoRA at all, where
        # eng.lora is None too; every chunk call below passes eng.lora
        # with THIS gates_row, so the bank is never un-gated
        # ---- chunk 0: B=1 prefix build, whole-page pool freeze
        toks0 = jnp.asarray([ids[:W]], jnp.int32)
        hist_s = dep.slm_build_prefix(eng.slm_params, toks0, eng.lora,
                                      gates_row)
        if self.s_cache is None:
            self._alloc(eng.slm.cfg.vocab_size,
                        None if gates_row is None
                        else gates_row.shape[-1])
        content = eng.slm.prefix_page_rows(hist_s, W, ps, eng.max_seq)
        self.s_cache = dep.insert_slm_prefix(
            self.s_cache, content,
            jnp.asarray(j.rows_s.full[:W // ps], jnp.int32))
        hist_l = None
        if self.use_cloud:
            hist_l = dep.llm_build_prefix(eng.llm_params, toks0)
            content_l = eng.llm.prefix_page_rows(hist_l, W, ps,
                                                 eng.max_seq)
            self.l_cache = dep.insert_llm_prefix(
                self.l_cache, content_l,
                jnp.asarray(j.rows_l.full[:W // ps], jnp.int32))
        # ---- middle chunks: exact width, one dispatch per chunk
        pre = W
        while len(ids) - pre > W:
            toks = jnp.asarray([ids[pre:pre + W]], jnp.int32)
            lens = jnp.asarray([W], jnp.int32)
            _, rows_s, hist_s = dep.slm_prefill_chunk(
                eng.slm_params, toks, lens, hist_s, eng.lora,
                gates_row, pre)
            self._insert_chunk("s", rows_s, j.slot, j.rows_s, pre, W)
            if self.use_cloud:
                _, rows_l, hist_l = dep.llm_prefill_chunk(
                    eng.llm_params, toks, lens, hist_l, pre)
                self._insert_chunk("l", rows_l, j.slot, j.rows_l,
                                   pre, W)
            pre += W
        # ---- final ragged chunk: ring/local + pos + decode logits
        w = len(ids) - pre
        wpad = PAG.pages_for(w, ps) * ps
        toks = np.zeros((1, wpad), np.int32)
        toks[0, :w] = ids[pre:]
        toks_j = jnp.asarray(toks)
        lens = jnp.asarray([w], jnp.int32)
        s_logits, rows_s = dep.slm_prefill_suffix(
            eng.slm_params, toks_j, lens, hist_s, eng.lora, gates_row,
            pre, pre)
        self._insert_chunk("s", rows_s, j.slot, j.rows_s, pre, wpad,
                           last=True)
        src = jnp.zeros((1,), jnp.int32)
        dst = jnp.asarray([j.slot], jnp.int32)
        self.sl = dep.insert_row(self.sl, s_logits[:, 0], src, dst)
        if self.use_cloud:
            l_logits, rows_l = dep.llm_prefill_suffix(
                eng.llm_params, toks_j, lens, hist_l, pre, pre)
            self._insert_chunk("l", rows_l, j.slot, j.rows_l, pre,
                               wpad, last=True)
            self.ll = dep.insert_row(self.ll, l_logits[:, 0], src, dst)
        if gates_row is not None:
            self.gates = dep.insert_row(self.gates, gates_row, src, dst)
        self._finish_admit(j)

    def _insert_chunk(self, which: str, rows, slot: int, rowpages,
                      pre: int, width: int, last: bool = False):
        """Scatter one chunk's page content at the row's reserved pages
        [pre/ps, (pre+width)/ps) through the SAME sharded paged-insert
        entry point as admission (pool pages stay sharded over
        ("pod","data")).  Middle chunks drop their ring/local pool
        content (dpl = NO_PAGE — only the final chunk's window is the
        row's real ring); table rows and pos are rewritten every chunk,
        idempotently, ending at the full-prompt state."""
        dep = self.eng.dep
        ps = dep.page_size
        pager = self.pager_s if which == "s" else self.pager_l
        np_c = width // ps
        dpf = jnp.asarray(
            [rowpages.full[pre // ps: pre // ps + np_c]], jnp.int32)
        block = jnp.asarray(np.asarray(pager.table_row(rowpages))[None])
        if pager.nl:
            local = jnp.asarray(
                np.asarray(pager.local_row(rowpages))[None])
        else:
            local = jnp.zeros((1, 0), jnp.int32)
        dpl = local if last else jnp.full_like(local, PAG.NO_PAGE)
        src = jnp.zeros((1,), jnp.int32)
        dst = jnp.asarray([slot], jnp.int32)
        ins = (dep.insert_slm_paged if which == "s"
               else dep.insert_llm_paged)
        cache = self.s_cache if which == "s" else self.l_cache
        cache = ins(cache, rows, src, dst, dpf, dpl, block, local)
        if which == "s":
            self.s_cache = cache
        else:
            self.l_cache = cache

    # ----------------------------------------------------- deadline cancel
    def _cancel_row(self, i: int, s: _Slot) -> Tuple[int, str, GenStats]:
        """Cancel an occupied row whose simulated clock passed its
        deadline: partial text surfaces with ``cancelled`` set, the
        adapter pin drops.  The caller parks/releases the device row."""
        st = s.stats
        st.cancelled = True
        self.eng._health["cancellations"] += 1
        self.eng._release_adapter(s)
        self.slots[i] = None
        return (s.rid, TOK.decode(s.out_ids), st)

    def _cancel_expired(self) -> List[Tuple[int, str, GenStats]]:
        """Boundary sweep: cancel every request past its deadline —
        occupied rows (pages released / dense rows parked) AND
        evicted-but-unfinished requests still queued for re-admission
        (they hold no pages, only a completion debt)."""
        out: List[Tuple[int, str, GenStats]] = []
        keep: List[_Slot] = []
        for s in self._evictq:
            if s.deadline_ms is not None \
                    and s.stats.clock_ms >= s.deadline_ms:
                s.stats.cancelled = True
                self.eng._health["cancellations"] += 1
                self.eng._release_adapter(s)
                out.append((s.rid, TOK.decode(s.out_ids), s.stats))
            else:
                keep.append(s)
        self._evictq = keep
        freed: List[int] = []
        for i, s in enumerate(self.slots):
            if s is None or s.deadline_ms is None:
                continue
            if s.stats.clock_ms >= s.deadline_ms:
                out.append(self._cancel_row(i, s))
                freed.append(i)
        if freed:
            self._park_rows(freed)
        return out

    # ------------------------------------------------------------- decode
    def step(self) -> List[Tuple[int, str, GenStats]]:
        """One fused decode step over every occupied row (the per-step
        reference path, ``macro_k=0``).  Returns the requests that
        finished this step as (rid, text, stats).

        This path pays multiple jit dispatches and 2-3 blocking host
        syncs per token; ``macro_step`` collapses the same math into one
        dispatch + one sync per K tokens and must stay bit-identical."""
        eng = self.eng
        dep = eng.dep
        done0 = self._cancel_expired()
        self._readmit_evicted()
        done0 += self._provision(1)
        if self.active == 0:
            return done0
        b = self.batch
        fault = eng.fault if self.use_cloud else None
        if self.use_cloud:
            occ = np.zeros((b,), bool)
            rids = np.zeros((b,), np.int32)
            steps = np.zeros((b,), np.int32)
            for i, s in enumerate(self.slots):
                if s is not None and not s.parked:
                    occ[i], rids[i], steps[i] = True, s.rid, len(s.out_ids)
            # one vectorized counter-based draw for the whole batch —
            # the same threefry weather the macro-step scan draws
            lat_d, ok_d = dep.lat_batched(jnp.asarray(rids),
                                          jnp.asarray(steps))
            lat = np.asarray(lat_d).copy()
            ok = np.asarray(ok_d)
            if fault is not None:
                # identical fault weather to the macro scan, then the
                # per-row breaker mirror advances on the host (it IS
                # the authoritative state on this path)
                lost_d, _ = dep.fault_batched(jnp.asarray(rids),
                                              jnp.asarray(steps))
                lost_h = np.asarray(lost_d)
                degraded = np.zeros((b,), bool)
                raws = np.zeros((b,), bool)
                edge32, fb32 = eng._fault_f32()
                for i, s in enumerate(self.slots):
                    if s is None or s.parked:
                        continue
                    deg, raw = eng._mirror_breaker(
                        s, bool(lost_h[i]), len(s.out_ids))
                    degraded[i], raws[i] = deg, raw
                    if deg:
                        lat[i] = edge32
                    elif raw:
                        lat[i] = fb32
                arrived = OPS.cloud_arrival_mask(ok, occ, raws,
                                                 degraded=degraded)
            else:
                degraded = np.zeros((b,), bool)
                arrived = OPS.cloud_arrival_mask(ok, occ)
            probs, w = dep.fuse_batched(dep.mlp, self.sl, self.ll,
                                        jnp.asarray(arrived))
        else:
            probs = dep.softmax_batched(self.sl)
            w = jnp.ones((b,))
        nxt_greedy = np.asarray(dep.argmax_batched(probs))
        w_host = np.asarray(w)
        nxt_sampled = None
        if any(s is not None and not s.parked and not s.greedy
               for s in self.slots):
            # on-device vmapped categorical over the fused distribution —
            # one dispatch for the whole batch instead of a per-row host
            # loop; keys fold_in(key_id, step) match the sequential
            # engine (key_id defaults to rid; a per-request seed from
            # Scheduler.submit overrides it)
            rids = np.zeros((b,), np.int32)
            steps = np.zeros((b,), np.int32)
            for i, s in enumerate(self.slots):
                if s is not None and not s.parked:
                    rids[i] = s.rid if s.key_id is None else s.key_id
                    steps[i] = len(s.out_ids)
            nxt_sampled = np.asarray(dep.sample_batched(
                probs, jnp.asarray(rids), jnp.asarray(steps)))

        done: List[Tuple[int, str, GenStats]] = []
        freed: List[int] = []
        next_tok = np.zeros((b, 1), np.int32)
        for i, s in enumerate(self.slots):
            if s is None or s.parked:
                continue
            st = s.stats
            if self.use_cloud:
                st.cloud_tokens += int(arrived[i])
                st.fallback_tokens += int(not arrived[i])
                st.cloud_calls += int(not degraded[i])
                st.push_latency(float(lat[i]))
            else:
                st.push_latency(float(eng.latency.edge_compute_ms))
            st.fusion_w.append(float(w_host[i]))
            nxt = int(nxt_greedy[i]) if s.greedy else int(nxt_sampled[i])
            s.out_ids.append(nxt)
            st.tokens += 1
            if nxt == TOK.EOS or len(s.out_ids) >= s.max_new:
                done.append((s.rid, TOK.decode(s.out_ids), st))
                eng._release_adapter(s)
                self.slots[i] = None        # freed: admit into this row
                freed.append(i)
            else:
                next_tok[i, 0] = nxt

        if freed:
            # park even when the lane fully drains: a later partial
            # admission must not revive stale rows at live positions
            self._park_rows(freed)
        parked_idx = [i for i, s in enumerate(self.slots)
                      if s is not None and s.parked]
        if any(s is not None and not s.parked for s in self.slots):
            # parked rows ride along (fixed-width batch) with pos at
            # FREED_POS — writes drop, pos frozen — and get their
            # pending logits restored after the dispatch
            old_sl, old_ll = self.sl, self.ll
            toks = jnp.asarray(next_tok)
            s_logits, self.s_cache = dep.slm_decode(
                eng.slm_params, self.s_cache, toks, eng.lora,
                self._decode_gates())
            self.sl = s_logits[:, 0]
            if self.use_cloud:
                l_logits, self.l_cache = dep.llm_decode(
                    eng.llm_params, self.l_cache, toks)
                self.ll = l_logits[:, 0]
            if parked_idx:
                idx = jnp.asarray(parked_idx, jnp.int32)
                self.sl = dep.insert_row(self.sl, old_sl, idx, idx)
                if self.use_cloud:
                    self.ll = dep.insert_row(self.ll, old_ll, idx, idx)
        return done0 + done

    def _park_rows(self, freed: List[int]):
        """Park freed rows at ATT.FREED_POS: the fixed-width batch still
        spends their FLOPs (rows can't be skipped mid-batch), but the
        decode scatter drops their cache writes — no garbage KV at
        advancing positions, no garbage ring-slot writes — and their
        position stops advancing (models/model.py freezes pos at the
        sentinel).  Re-admission scatters a whole fresh row cache, so
        parity with an unparked engine is unchanged."""
        if self.eng.paged:
            self._release_rows(freed)
            return
        idx = jnp.asarray(freed, jnp.int32)
        self.s_cache = dict(
            self.s_cache,
            pos=self.s_cache["pos"].at[idx].set(ATT.FREED_POS))
        if self.use_cloud:
            self.l_cache = dict(
                self.l_cache,
                pos=self.l_cache["pos"].at[idx].set(ATT.FREED_POS))

    def _release_rows(self, freed: List[int]):
        """Paged parking releases memory for real: pos to FREED_POS AND
        block/local table rows to NO_PAGE on device (writes drop,
        gathers clamp onto masked garbage), then the pages go back to
        the host free lists for the next admission.  Safe against the
        decode still consuming the old buffers — the sentineled tables
        mean the parked row can never touch a re-issued page."""
        dep = self.eng.dep
        idx = jnp.asarray(freed, jnp.int32)
        self.s_cache = dep.free_paged_rows(self.s_cache, idx)
        if self.use_cloud:
            self.l_cache = dep.free_paged_rows(self.l_cache, idx)
        for i in freed:
            self.pager_s.release(i)
            if self.pager_l is not None:
                self.pager_l.release(i)

    # ------------------------------------------------------- lazy growth
    def _set_positions(self, updates: List[Tuple[int, int]]):
        """Batched row-pos park/unpark on both caches: (row, pos)
        pairs, padded to a power of two with out-of-range rows
        (mode=\"drop\") so retraces stay bounded."""
        if not updates:
            return
        dep = self.eng.dep
        n = 1 << (len(updates) - 1).bit_length()
        idx = np.full((n,), self.batch, np.int32)
        val = np.zeros((n,), np.int32)
        for t, (i, v) in enumerate(updates):
            idx[t], val[t] = i, v
        idx_j, val_j = jnp.asarray(idx), jnp.asarray(val)
        self.s_cache = dep.set_row_pos(self.s_cache, idx_j, val_j)
        if self.use_cloud:
            if self._spec:
                # unparks restore the one-behind LLM depth p-1; park
                # sentinels (>= FREED_POS) pass through untouched
                val = np.where(val < ATT.FREED_POS, val - 1, val)
                val_j = jnp.asarray(val)
            self.l_cache = dep.set_row_pos(self.l_cache, idx_j, val_j)

    def _apply_growth(self, which: str, ups: List[Tuple[int, int, int]]):
        """ONE padded block-table scatter per model per boundary for
        all rows' freshly grown pages."""
        if not ups:
            return
        dep = self.eng.dep
        n = 1 << (len(ups) - 1).bit_length()
        rows = np.full((n,), self.batch, np.int32)
        cols = np.zeros((n,), np.int32)
        pids = np.zeros((n,), np.int32)
        for t, (r, c, p) in enumerate(ups):
            rows[t], cols[t], pids[t] = r, c, p
        args = (jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(pids))
        if which == "s":
            self.s_cache = dep.grow_block_pages(self.s_cache, *args)
        else:
            self.l_cache = dep.grow_block_pages(self.l_cache, *args)

    def _grow_row(self, i: int, s: _Slot, k: int, ups_s, ups_l) -> bool:
        """Ensure row ``i`` has pages for its next (up to) ``k`` decode
        writes.  Token n writes at position prompt_len + n and the last
        selected token is never fed, so a row with <= 1 budget left
        writes nothing — EOS rows never claim their tail.  Growth is
        atomic across both pagers (rolled back on a partial success);
        True means the row can decode this boundary."""
        ps = self.eng.dep.page_size
        n = len(s.out_ids)
        rem = s.max_new - n
        if rem <= 1:
            return True
        hi = s.prompt_len + n + min(k, rem - 1) - 1
        need = hi // ps + 1
        g_s = need - len(self.pager_s.rows[i].full)
        g_l = 0
        if self.use_cloud:
            g_l = need - len(self.pager_l.rows[i].full)
        if g_s <= 0 and g_l <= 0:
            return True
        got_s = self.pager_s.grow(i, g_s) if g_s > 0 else []
        if got_s is None:
            return False
        got_l: List[int] = []
        if g_l > 0:
            got_l = self.pager_l.grow(i, g_l)
            if got_l is None:
                if got_s:
                    self.pager_s.ungrow(i, got_s)
                return False
        for t, pid in enumerate(got_s):
            ups_s.append((i, need - g_s + t, pid))
        for t, pid in enumerate(got_l):
            ups_l.append((i, need - g_l + t, pid))
        self.eng._stat["grown_pages"] += len(got_s) + len(got_l)
        return True

    def _provision(self, k: int) -> List[Tuple[int, str, GenStats]]:
        """Lazy-growth pass at a decode boundary: extend live rows'
        block tables (oldest admission first — deterministic page
        handout and no starvation among waiters) before the next k
        tokens dispatch.  A row whose growth can't be satisfied PARKS:
        pos -> FREED_POS (its row still spends batch FLOPs but every
        cache write drops) with its pending logits preserved, so it
        resumes bit-identically once pages free.  If EVERY live row is
        parked the lane is wedged and the youngest rows are EVICTED
        (pages released, request re-admitted internally from prompt +
        tokens-so-far) until the oldest grows — the hard admission gate
        bounds each row's worst case by pool capacity, so a lone row
        always completes and growth can never deadlock a full pool.  A
        lone row that STILL can't grow (pages pinned outside row
        accounting, e.g. a prefix registry) is force-completed with the
        tokens it has rather than spinning forever.  Worst-case mode
        (lazy_pages=False) reserves everything at admission: this pass
        issues no device op at all."""
        eng = self.eng
        if not eng.paged or not eng.lazy_pages:
            return []
        forced: List[Tuple[int, str, GenStats]] = []
        while True:
            order = sorted(
                (i for i, s in enumerate(self.slots) if s is not None),
                key=lambda i: self.slots[i].seq)
            if not order:
                return forced
            ups_s: List[Tuple[int, int, int]] = []
            ups_l: List[Tuple[int, int, int]] = []
            pos_ups: List[Tuple[int, int]] = []
            any_active = False
            for i in order:
                s = self.slots[i]
                if self._grow_row(i, s, k, ups_s, ups_l):
                    if s.parked:
                        s.parked = False
                        pos_ups.append((i, s.prompt_len
                                        + len(s.out_ids)))
                    any_active = True
                elif not s.parked:
                    s.parked = True
                    pos_ups.append((i, ATT.FREED_POS))
                    eng._stat["parks"] += 1
            self._apply_growth("s", ups_s)
            if self.use_cloud:
                self._apply_growth("l", ups_l)
            self._set_positions(pos_ups)
            if any_active:
                return forced
            if len(order) > 1:
                self._evict(order[-1])      # youngest first
                continue
            i = order[0]
            s = self.slots[i]
            forced.append((s.rid, TOK.decode(s.out_ids), s.stats))
            eng._release_adapter(s)
            self.slots[i] = None
            self._release_rows([i])
            eng._stat["forced"] += 1

    def _evict(self, i: int):
        """Release a parked row's pages and queue its request for
        internal re-admission: prompt + all selected tokens re-prefill
        later, landing on exactly the distribution it was parked on
        (prefill's last-position logits ARE the next selection's)."""
        s = self.slots[i]
        self.slots[i] = None
        self._release_rows([i])
        self._evictq.append(s)
        self.eng._stat["evictions"] += 1

    def _readmit_evicted(self):
        """Re-admit evicted requests, oldest first, into freed slots/
        pages.  The admission gate refuses external requests while any
        eviction is pending, so FIFO order survives eviction; a blocked
        head blocks the rest (no overtake)."""
        if not self._evictq:
            return
        eng = self.eng
        self._evictq.sort(key=lambda s: s.seq)
        free = self.free_slots()
        jobs: List[_PagedJob] = []
        while self._evictq and free:
            s = self._evictq[0]
            ids = list(s.prompt_ids) + list(s.out_ids)
            alloc_len = min(s.prompt_len + s.max_new, eng.max_ctx)
            cap = PAG.pages_for(alloc_len, eng.dep.page_size)
            nf, nl = self.pager_s.demand_lazy(len(ids), alloc_len)
            ok = self.pager_s.fits_free(nf, nl)
            if ok and self.use_cloud:
                nf_l, nl_l = self.pager_l.demand_lazy(len(ids),
                                                      alloc_len)
                ok = self.pager_l.fits_free(nf_l, nl_l)
            if not ok:
                break
            slot = free.pop(0)
            rows_s = self.pager_s.admit(slot, nf, cap_pages=cap)
            rows_l = None
            if self.use_cloud:
                rows_l = self.pager_l.admit(slot, nf_l, cap_pages=cap)
            jobs.append(_PagedJob(
                slot, s.full_text, s.max_new, s.greedy, s.rid,
                s.stats.private, s.key_id, ids, rows_s, rows_l, None,
                seq=s.seq, resume=s, aslot=s.aslot))
            self._evictq.pop(0)
        if jobs:
            self._admit_paged(jobs)

    # -------------------------------------------------------- macro decode
    def macro_dispatch(self, k: int):
        """Dispatch a K-token macro-step for every occupied row in ONE
        jitted, cache-donating call (an on-device ``lax.scan`` over the
        whole per-token step: latency draws, fusion, select/sample, EOS
        + park masks, SLM+LLM decode) WITHOUT the host sync — the
        returned trace arrays are stashed for ``macro_collect``.

        The lane's cache/logit buffers are DONATED to the dispatch —
        any reference taken before this call is invalid afterwards.
        Between dispatch and collect the host is free to run admission
        (tokenize + packed prefill + row scatter) against the macro's
        output caches: that is the scheduler's admission-pipelining
        overlap.  No-op when the lane is idle or a macro is already in
        flight."""
        eng = self.eng
        dep = eng.dep
        if self._inflight is not None:
            return
        self._pending_done.extend(self._cancel_expired())
        self._readmit_evicted()
        self._pending_done.extend(self._provision(k))
        if self.active == 0:
            return
        b = self.batch
        rids = np.zeros((b,), np.int32)
        keys = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        maxn = np.zeros((b,), np.int32)
        greedy = np.ones((b,), bool)
        done = np.ones((b,), bool)
        # circuit-breaker state enters the scan from the slots' host
        # mirrors (bit-equal to the carry the last scan returned — the
        # mirror replays the identical recurrence) so admission resets
        # and eviction/resume never need a device fetch or scatter
        bfails = np.zeros((b,), np.int32)
        bcool = np.zeros((b,), np.int32)
        for i, s in enumerate(self.slots):
            if s is None or s.parked:
                # parked-for-growth rows stay done for the whole scan:
                # trace emit all-False, pending logits preserved by the
                # macro body's keep mask
                continue
            done[i] = False
            rids[i] = s.rid
            keys[i] = s.rid if s.key_id is None else s.key_id
            steps[i] = len(s.out_ids)
            maxn[i] = s.max_new
            greedy[i] = s.greedy
            bfails[i], bcool[i] = s.bfails, s.bcool
        sample = bool((~greedy & ~done).any())
        fn = dep.macro_cloud if self.use_cloud else dep.macro_edge
        carry, traces = fn(
            eng.slm_params, eng.llm_params if self.use_cloud else None,
            dep.mlp if self.use_cloud else None,
            eng.lora, self._decode_gates(),
            self.s_cache, self.l_cache, self.sl, self.ll,
            jnp.asarray(bfails), jnp.asarray(bcool),
            jnp.asarray(rids), jnp.asarray(keys), jnp.asarray(steps),
            jnp.asarray(maxn), jnp.asarray(greedy), jnp.asarray(done),
            k, sample)
        self.s_cache, self.l_cache, self.sl, self.ll = carry[:4]
        self._inflight = (k, traces)

    def macro_collect(self) -> List[Tuple[int, str, GenStats]]:
        """The ONE host sync of an in-flight macro-step: fetch the
        stacked traces and replay them into the slot bookkeeping.
        Returns the requests that finished during the macro-step.
        Rows admitted between dispatch and collect were parked for the
        whole scan (emit mask all-False), so the replay skips them."""
        eng = self.eng
        if self._inflight is None:
            out_done = self._pending_done
            self._pending_done = []
            return out_done
        k, traces = self._inflight
        self._inflight = None
        toks, arrived, lat, w, emit, lost = eng.dep.fetch_traces(traces)
        fault = eng.fault if self.use_cloud else None

        out_done: List[Tuple[int, str, GenStats]] = []
        out_done.extend(self._pending_done)
        self._pending_done = []
        freed: List[int] = []
        cancelled: List[int] = []
        for t in range(k):
            for i, s in enumerate(self.slots):
                if s is None or not emit[t, i]:
                    continue
                st = s.stats
                if s.deadline_ms is not None \
                        and st.clock_ms >= s.deadline_ms:
                    # the deadline expired mid-macro: token t (and the
                    # rest of this row's trace) is discarded — the same
                    # "emit iff the clock after t-1 is under deadline"
                    # rule the per-token path applies at its step top
                    out_done.append(self._cancel_row(i, s))
                    cancelled.append(i)
                    continue
                deg = False
                if fault is not None:
                    # replay the breaker mirror on the traced loss draw
                    # + host-recomputed outage schedule; emit == the
                    # scan's active mask, so the mirror sees exactly
                    # the transitions the device carry integrated
                    deg, _ = eng._mirror_breaker(s, bool(lost[t, i]),
                                                 len(s.out_ids))
                if self.use_cloud:
                    st.cloud_tokens += int(arrived[t, i])
                    st.fallback_tokens += int(not arrived[t, i])
                    st.cloud_calls += int(not deg)
                    st.push_latency(float(lat[t, i]))
                    st.fusion_w.append(float(w[t, i]))
                else:
                    st.push_latency(float(eng.latency.edge_compute_ms))
                    st.fusion_w.append(1.0)
                nxt = int(toks[t, i])
                s.out_ids.append(nxt)
                st.tokens += 1
                if nxt == TOK.EOS or len(s.out_ids) >= s.max_new:
                    out_done.append((s.rid, TOK.decode(s.out_ids), st))
                    eng._release_adapter(s)
                    self.slots[i] = None    # freed: refill next boundary
                    freed.append(i)
        if cancelled:
            # cancelled rows were still live on device (the scan knows
            # no deadlines) — park/release them explicitly
            self._park_rows(cancelled)
        if freed and eng.paged:
            # drained rows were parked in-scan; now return their pages
            # (dense rows stay parked-but-resident until re-admission)
            self._release_rows(freed)
        return out_done

    def macro_step(self, k: int) -> List[Tuple[int, str, GenStats]]:
        """Dispatch + collect in one call: decode K tokens for every
        occupied row in ONE jitted dispatch with ONE host sync.
        Bit-identical to running ``step()`` k times: rows that finish
        mid-macro keep decoding as parked rows (writes dropped, pos
        frozen) and their freed slots refill at the next boundary."""
        self.macro_dispatch(k)
        return self.macro_collect()

    # -------------------------------------------------- speculative decode
    def _row_pos(self, cache, updates: List[Tuple[int, int]]):
        """Single-cache row-pos scatter (``_set_positions`` touches both
        caches symmetrically; the spec seed needs them independently),
        padded to a power of two like every other host-batched update."""
        dep = self.eng.dep
        n = 1 << (len(updates) - 1).bit_length()
        idx = np.full((n,), self.batch, np.int32)
        val = np.zeros((n,), np.int32)
        for t, (i, v) in enumerate(updates):
            idx[t], val[t] = i, v
        return dep.set_row_pos(cache, jnp.asarray(idx), jnp.asarray(val))

    def _spec_seed(self):
        """Move freshly admitted (and eviction-resumed) rows onto the
        speculative protocol invariant: SLM at depth p = prompt_len + n
        with ``sl`` predicting emit n, LLM ONE BEHIND at depth p-1 with
        the last emitted token pending in ``lt``.

        Fresh rows (no tokens yet) emit their FIRST token here exactly
        like the per-token path — prefill left both models at prompt
        depth, so the entry (sl, ll) pair IS the baseline fusion for
        emit 0; the selected token is then fed to the SLM ONLY, which
        lands the row precisely one-behind without ever rewinding the
        LLM.  Eviction-resumed rows came back from a full re-prefill
        (depth p on both models): the LLM row pos is rewound to p-1 and
        the last emitted token re-pended in ``lt`` — the next burst's
        first verify feed rewrites slot p-1 with the identical (token,
        position) KV, so the rewind is bitwise free (prefill == decode,
        the PR 7 eviction-resume contract)."""
        eng = self.eng
        dep = eng.dep
        fresh = [i for i, s in enumerate(self.slots)
                 if s is not None and not s.parked and not s.out_ids]
        init = [i for i, s in enumerate(self.slots)
                if s is not None and not s.parked and s.out_ids
                and s.needs_spec_init]
        if init:
            self.l_cache = self._row_pos(
                self.l_cache,
                [(i, self.slots[i].prompt_len
                  + len(self.slots[i].out_ids) - 1) for i in init])
            idx = jnp.asarray(init, jnp.int32)
            last = jnp.asarray([self.slots[i].out_ids[-1] for i in init],
                               jnp.int32)
            self.lt = dep.insert_row(self.lt, last,
                                     jnp.arange(len(init)), idx)
            for i in init:
                self.slots[i].needs_spec_init = False
        if not fresh:
            return
        b = self.batch
        fault = eng.fault
        occ = np.zeros((b,), bool)
        rids = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        for i in fresh:
            occ[i], rids[i] = True, self.slots[i].rid
        lat_d, ok_d = dep.lat_batched(jnp.asarray(rids),
                                      jnp.asarray(steps))
        lat = np.asarray(lat_d).copy()
        ok = np.asarray(ok_d)
        degraded = np.zeros((b,), bool)
        if fault is not None:
            lost_d, _ = dep.fault_batched(jnp.asarray(rids),
                                          jnp.asarray(steps))
            lost_h = np.asarray(lost_d)
            raws = np.zeros((b,), bool)
            edge32, fb32 = eng._fault_f32()
            for i in fresh:
                deg, raw = eng._mirror_breaker(self.slots[i],
                                               bool(lost_h[i]), 0)
                degraded[i], raws[i] = deg, raw
                if deg:
                    lat[i] = edge32
                elif raw:
                    lat[i] = fb32
            arrived = OPS.cloud_arrival_mask(ok, occ, raws,
                                             degraded=degraded)
        else:
            arrived = OPS.cloud_arrival_mask(ok, occ)
        probs, w = dep.fuse_batched(dep.mlp, self.sl, self.ll,
                                    jnp.asarray(arrived))
        nxt_greedy = np.asarray(dep.argmax_batched(probs))
        w_host = np.asarray(w)
        nxt_sampled = None
        if any(not self.slots[i].greedy for i in fresh):
            keys = np.zeros((b,), np.int32)
            for i in fresh:
                s = self.slots[i]
                keys[i] = s.rid if s.key_id is None else s.key_id
            nxt_sampled = np.asarray(dep.sample_batched(
                probs, jnp.asarray(keys), jnp.asarray(steps)))
        feed = np.zeros((b, 1), np.int32)
        fed: List[int] = []
        freed: List[int] = []
        for i in fresh:
            s = self.slots[i]
            s.needs_spec_init = False
            st = s.stats
            if s.deadline_ms is not None and st.clock_ms >= s.deadline_ms:
                self._pending_done.append(self._cancel_row(i, s))
                freed.append(i)
                continue
            st.cloud_tokens += int(arrived[i])
            st.fallback_tokens += int(not arrived[i])
            st.cloud_calls += int(not degraded[i])
            st.push_latency(float(lat[i]))
            st.fusion_w.append(float(w_host[i]))
            nxt = int(nxt_greedy[i]) if s.greedy else int(nxt_sampled[i])
            s.out_ids.append(nxt)
            st.tokens += 1
            if nxt == TOK.EOS or len(s.out_ids) >= s.max_new:
                self._pending_done.append(
                    (s.rid, TOK.decode(s.out_ids), st))
                eng._release_adapter(s)
                self.slots[i] = None
                freed.append(i)
            else:
                feed[i, 0] = nxt
                fed.append(i)
        if freed:
            self._park_rows(freed)
        if not fed:
            return
        # feed the seed tokens to the SLM ONLY: every other live row is
        # parked for this one decode (writes drop at FREED_POS) and gets
        # its pending logits restored right after
        others = [(i, s.prompt_len + len(s.out_ids))
                  for i, s in enumerate(self.slots)
                  if s is not None and not s.parked and i not in fed]
        if others:
            self.s_cache = self._row_pos(
                self.s_cache, [(i, ATT.FREED_POS) for i, _ in others])
        old_sl = self.sl
        s_logits, self.s_cache = dep.slm_decode(
            eng.slm_params, self.s_cache, jnp.asarray(feed), eng.lora,
            self._decode_gates())
        self.sl = s_logits[:, 0]
        keep = [i for i, s in enumerate(self.slots)
                if s is not None and i not in fed]
        if keep:
            idx = jnp.asarray(keep, jnp.int32)
            self.sl = dep.insert_row(self.sl, old_sl, idx, idx)
        fed_j = jnp.asarray(fed, jnp.int32)
        self.lt = dep.insert_row(self.lt, jnp.asarray(feed[:, 0]),
                                 fed_j, fed_j)
        if others:
            self.s_cache = self._row_pos(self.s_cache, others)

    def spec_dispatch(self, n_bursts: int, k: int):
        """Dispatch ``n_bursts`` chained speculative bursts (tentpole
        PR 10) WITHOUT a host sync: each burst drafts k tokens on the
        SLM, verifies all k positions in ONE LLM dispatch, and rolls
        rejected writes back on-device; the device carry (caches,
        logits, ``lt``, breaker state, steps/done) threads straight
        into the next burst.  LLM verify dispatches == ``spec_cloud``
        invocations == n_bursts — the countable dispatch-discipline
        contract.  Per-burst traces are stashed for ``spec_collect``'s
        single ``fetch_traces`` sync."""
        eng = self.eng
        dep = eng.dep
        if self._inflight is not None:
            return
        self._pending_done.extend(self._cancel_expired())
        self._readmit_evicted()
        # +1: the host-side seed token of a fresh row consumes one
        # provisioned write before the bursts even start
        self._pending_done.extend(self._provision(n_bursts * k + 1))
        if self.active:
            self._spec_seed()
        if self.active == 0:
            return
        b = self.batch
        rids = np.zeros((b,), np.int32)
        keys = np.zeros((b,), np.int32)
        steps = np.zeros((b,), np.int32)
        maxn = np.zeros((b,), np.int32)
        greedy = np.ones((b,), bool)
        done = np.ones((b,), bool)
        bfails = np.zeros((b,), np.int32)
        bcool = np.zeros((b,), np.int32)
        for i, s in enumerate(self.slots):
            if s is None or s.parked:
                continue
            done[i] = False
            rids[i] = s.rid
            keys[i] = s.rid if s.key_id is None else s.key_id
            steps[i] = len(s.out_ids)
            maxn[i] = s.max_new
            greedy[i] = s.greedy
            bfails[i], bcool[i] = s.bfails, s.bcool
        sample = bool((~greedy & ~done).any())
        gates = self._decode_gates()
        s_c, l_c, sl, lt = self.s_cache, self.l_cache, self.sl, self.lt
        fails_d, cool_d = jnp.asarray(bfails), jnp.asarray(bcool)
        steps_d, done_d = jnp.asarray(steps), jnp.asarray(done)
        rids_d, keys_d = jnp.asarray(rids), jnp.asarray(keys)
        maxn_d, greedy_d = jnp.asarray(maxn), jnp.asarray(greedy)
        bursts = []
        for _ in range(n_bursts):
            carry, traces = dep.spec_cloud(
                eng.slm_params, eng.llm_params, dep.mlp, eng.lora, gates,
                s_c, l_c, sl, lt, fails_d, cool_d,
                rids_d, keys_d, steps_d, maxn_d, greedy_d, done_d,
                k, sample)
            (s_c, l_c, sl, lt, fails_d, cool_d,
             steps_d, done_d) = carry
            bursts.append(traces)
        self.s_cache, self.l_cache, self.sl, self.lt = s_c, l_c, sl, lt
        self._inflight = ("spec", k, bursts)

    def spec_collect(self) -> List[Tuple[int, str, GenStats]]:
        """The ONE host sync of an in-flight burst chain: fetch every
        burst's traces together and replay them into the slot
        bookkeeping in burst order.  Token 0 of a burst is charged the
        burst's (single) cloud round-trip latency; the accepted draft
        tokens behind it cost the edge decode only — that is the
        latency shape speculation buys.  Per burst per row: one breaker
        transition (mirroring the device's per-burst recurrence),
        cloud_calls += 1 unless the row ran degraded, spec_drafted += k
        and spec_accepted += |accepted ∩ draft|."""
        eng = self.eng
        dep = eng.dep
        if self._inflight is None:
            out_done = self._pending_done
            self._pending_done = []
            return out_done
        _tag, k, bursts = self._inflight
        self._inflight = None
        fetched = dep.fetch_traces(bursts)
        fault = eng.fault
        edge32, _ = eng._fault_f32()
        out_done: List[Tuple[int, str, GenStats]] = []
        out_done.extend(self._pending_done)
        self._pending_done = []
        freed: List[int] = []
        cancelled: List[int] = []
        for (sels, n_emit, c_sel, arrived, lat, w, lost) in fetched:
            for i, s in enumerate(self.slots):
                if s is None or not n_emit[i]:
                    continue
                st = s.stats
                if s.deadline_ms is not None \
                        and st.clock_ms >= s.deadline_ms:
                    out_done.append(self._cancel_row(i, s))
                    cancelled.append(i)
                    continue
                deg = False
                if fault is not None:
                    deg, _raw = eng._mirror_breaker(
                        s, bool(lost[i]), len(s.out_ids))
                st.spec_drafted += k
                st.spec_accepted += int(min(n_emit[i], c_sel[i]))
                st.cloud_calls += int(not deg)
                if deg:
                    # the device charged ONE degraded breaker step for
                    # the whole burst; the remaining emitted tokens are
                    # degraded too (pure SLM drafting, zero cloud cost)
                    extra = int(n_emit[i]) - 1
                    st.degraded_tokens += extra
                    eng._health["degraded_tokens"] += extra
                for t in range(int(n_emit[i])):
                    if s.deadline_ms is not None \
                            and st.clock_ms >= s.deadline_ms:
                        out_done.append(self._cancel_row(i, s))
                        cancelled.append(i)
                        break
                    st.cloud_tokens += int(arrived[i])
                    st.fallback_tokens += int(not arrived[i])
                    st.push_latency(float(lat[i]) if t == 0 else edge32)
                    st.fusion_w.append(float(w[t, i]))
                    nxt = int(sels[t, i])
                    s.out_ids.append(nxt)
                    st.tokens += 1
                    if nxt == TOK.EOS or len(s.out_ids) >= s.max_new:
                        out_done.append(
                            (s.rid, TOK.decode(s.out_ids), st))
                        eng._release_adapter(s)
                        self.slots[i] = None
                        freed.append(i)
                        break
        if cancelled:
            # the burst chain knows no deadlines — cancelled rows are
            # still live on device and must be parked/released
            self._park_rows(cancelled)
        if freed and eng.paged:
            self._release_rows(freed)
        return out_done


class BatchedHybridEngine(HybridEngine):
    """Continuous-batching Floe engine (the paper's real-time serving
    claim at production shape).

    Two fixed-width decode batches ("lanes"): cloud-eligible requests
    share a hybrid SLM+LLM batch whose per-token fusion runs through the
    Pallas ``logit_fusion`` kernel with a per-row Sec. IV-D arrived
    mask; private requests share an SLM-only batch (Alg. 2 — they never
    touch the network path).  Admissions that arrive in the same step
    share one packed B>1 prefill (prompts padded to a chunk-rounded
    length, per-row lengths masked) and are scattered into freed rows as
    sequences hit EOS.  All dense-family cache layouts are supported —
    plain, grouped mixed-attention (gemma3 5:1), and window-sized ring
    caches with per-row ring indices.

    Decoding advances in **K-token macro-steps** (``macro_k``, default
    8): one jitted, cache-donating dispatch runs an on-device scan over
    the whole per-token pipeline and the host syncs once per K tokens to
    replay the returned traces into request bookkeeping.  ``step()``
    splits into ``dispatch_step()`` (enqueue the macro, no sync) and
    ``collect_step()`` (trace fetch + replay), so a scheduler can admit
    the next burst — tokenize, packed prefill, row scatter — while the
    macro is still executing (macro-boundary admission pipelining).
    DONATION CONTRACT: each macro-step consumes the lane's cache/logit
    buffers — callers must re-read ``lane.s_cache``/``lane.sl``/... after
    every step and never hold stale references across one.  ``macro_k=0``
    keeps the legacy per-token step path (multiple dispatches + syncs
    per token) as a bit-exact reference and benchmark baseline.

    Placement — the mesh, per-leaf param NamedShardings (SLM, LLM, LoRA
    bank, alignment MLP laid out by the launch/sharding.py rule sets so
    per-device param bytes shrink with the "model" axis), the lane-cache
    layout, and all compiled entry points — lives on the
    ``ServingDeployment`` (``deployment=``, or built internally from the
    legacy ``mesh=``/``rules=`` arguments).  Fused logits always come
    back replicated (the paper fuses at the edge), so the Pallas fusion
    kernel and sampling are untouched whatever the layout."""

    def __init__(self, slm=None, slm_params=None, llm=None, llm_params=None,
                 alignment_mlp=None, expert_bank=None,
                 router: Optional[Router] = None,
                 detector: Optional[PrivacyDetector] = None,
                 latency: Optional[LatencyModel] = None,
                 timeout_ms: float = 200.0, max_seq: int = 96,
                 sample_seed: int = 0, batch_size: int = 8,
                 edge_batch_size: Optional[int] = None, block_b: int = 8,
                 packed_prefill: bool = True, prefill_chunk: int = 16,
                 mesh=None, rules="inference", macro_k: int = 8,
                 paged: bool = True, pool_pages: Optional[int] = None,
                 local_pool_pages: Optional[int] = None,
                 llm_pool_pages: Optional[int] = None,
                 lazy_pages: bool = True,
                 chunk_width: Optional[int] = None,
                 spec_k: int = 0, use_slot_kernel: bool = False,
                 deployment: Optional[ServingDeployment] = None):
        if deployment is None:
            deployment = ServingDeployment(
                slm, slm_params, llm, llm_params, alignment_mlp,
                expert_bank=expert_bank, latency=latency,
                timeout_ms=timeout_ms, max_seq=max_seq,
                sample_seed=sample_seed, mesh=mesh, rules=rules,
                block_b=block_b)
        else:
            _reject_deployment_args(
                slm=(slm, None), slm_params=(slm_params, None),
                llm=(llm, None), llm_params=(llm_params, None),
                alignment_mlp=(alignment_mlp, None),
                expert_bank=(expert_bank, None), latency=(latency, None),
                timeout_ms=(timeout_ms, 200.0), max_seq=(max_seq, 96),
                sample_seed=(sample_seed, 0), mesh=(mesh, None),
                rules=(rules, "inference"), block_b=(block_b, 8))
        if deployment.llm is None:
            raise ValueError(
                "BatchedHybridEngine needs a hybrid (SLM+LLM) deployment;"
                " this one is SLM-only — serve it with SoloEngine")
        super().__init__(router=router, detector=detector,
                         deployment=deployment)
        for lm in (self.slm, self.llm):
            # the per-leaf batch-axis scatter covers every dense cache
            # layout; other families keep a scalar decode pos
            if lm.cfg.family != "dense":
                raise NotImplementedError(
                    "batched continuous decode supports dense-family "
                    f"models (got {lm.cfg.family})")
        self.packed_prefill = packed_prefill
        self.prefill_chunk = prefill_chunk
        self.macro_k = macro_k
        self.mesh = deployment.mesh
        self.rules = deployment.rules
        # paged lane KV (the default): page-pool + block-table caches,
        # page-gated admission and page release at EOS.  paged=False
        # keeps the dense stacked caches as the bit-exact parity oracle.
        self.paged = paged
        self.pool_pages = pool_pages
        self.local_pool_pages = local_pool_pages
        self.llm_pool_pages = llm_pool_pages
        # lazy_pages=False keeps the eager worst-case reservation (the
        # PR 6 path) as a bit-exact oracle: growth is never needed, so
        # the provisioning pass is a no-op
        self.lazy_pages = lazy_pages
        self.max_ctx = deployment.max_ctx
        # dense prefill buffer width for chunked long-prompt admission:
        # prompts beyond it stream page-chunk by page-chunk
        self.chunk_width = chunk_width or self.max_seq
        ps = deployment.page_size
        assert (self.chunk_width % ps == 0
                and ps <= self.chunk_width <= self.max_seq), \
            f"chunk_width={self.chunk_width} must be page-aligned in " \
            f"[{ps}, {self.max_seq}]"
        # speculative decode (tentpole PR 10): spec_k > 0 switches the
        # cloud lane to draft/verify bursts of k tokens per LLM
        # dispatch; spec_k = 0 keeps the per-token/macro paths as the
        # bit-exact oracle.  The k draft slots of a burst must be
        # DISTINCT cache slots for snapshot/rollback, so k is bounded
        # by any ring window in either model's cache layout.
        if spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0")
        if spec_k:
            for lm in (self.slm, self.llm):
                loc = lm._ring_local_len(self.max_seq)
                if loc and spec_k > loc:
                    raise ValueError(
                        f"spec_k={spec_k} exceeds the {loc}-slot ring "
                        f"window of {lm.cfg.name}: a draft burst would "
                        "wrap the ring and its rollback snapshot would "
                        "alias slots")
        self.spec_k = spec_k
        # satellite: route decode-time LoRA through the scalar-prefetch
        # slot-gather kernel instead of the dense one-hot einsum
        self.use_slot_kernel = use_slot_kernel
        self._seq = 0
        self._stat = dict(grown_pages=0, parks=0, evictions=0, forced=0)
        self._rejected: List[Tuple[int, str]] = []
        self.cloud_lane = _Lane(self, batch_size, use_cloud=True)
        self.edge_lane = _Lane(self, edge_batch_size or batch_size,
                               use_cloud=False)

    def _next_seq(self) -> int:
        s = self._seq
        self._seq += 1
        return s

    def growth_stats(self) -> Dict[str, int]:
        """Lazy-growth counters: pages grown at boundaries, rows parked
        for backpressure, evictions, forced completions."""
        return dict(self._stat)

    def _make_pager(self, lm, batch: int) -> PAG.LanePager:
        """Host page bookkeeping for one (lane, model).  Default pool
        budgets are the dense equivalent (batch x full table width), so
        a default paged engine can always admit what the dense engine
        could; ``pool_pages``/``local_pool_pages`` shrink the pools to
        serve MORE concurrent mixed-length rows in the same bytes (the
        capacity-sweep benchmark's knob)."""
        geo = self.dep.paged_geometry(lm)
        pages = (self.pool_pages if self.pool_pages is not None
                 else batch * geo["nb"])
        if lm is self.dep.llm and self.llm_pool_pages is not None:
            pages = self.llm_pool_pages
        lp = (self.local_pool_pages if self.local_pool_pages is not None
              else batch * geo["nl"])
        pager = PAG.LanePager(batch, self.max_seq, self.dep.page_size,
                              pages, geo["local_len"], lp,
                              max_ctx=self.max_ctx)
        pager.geo = geo
        return pager

    # ------------------------------------------------------------- public
    def has_capacity(self, private: bool) -> bool:
        lane = self.edge_lane if private else self.cloud_lane
        return lane.free_slot() is not None

    def add_request(self, prompt: str, max_new_tokens: int = 16,
                    greedy: bool = True, rid: int = 0,
                    seed: Optional[int] = None,
                    prefix: Optional[str] = None,
                    adapter_id: Optional[Any] = None,
                    deadline_ms: Optional[float] = None) -> bool:
        """Admit a request into its lane; False if it couldn't be
        admitted (lane full, or — paged — not enough free pages, or no
        adapter slot free for ``adapter_id``; a page demand beyond total
        pool capacity or an UNKNOWN adapter id is a HARD reject surfaced
        via ``pop_rejected`` and never retried).  ``deadline_ms`` bounds
        the request's simulated decode clock — passed, it is cancelled
        at the next decode boundary with its partial text."""
        return self.add_requests([(prompt, max_new_tokens, greedy,
                                   rid, seed, prefix, adapter_id,
                                   deadline_ms)])[0]

    def _adapter_reject_msg(self, aid) -> str:
        if self.adapters is None:
            return (f"adapter_id={aid!r} on an engine without adapter "
                    "slots — build the ServingDeployment with "
                    "adapter_slots=")
        return (f"unknown adapter id {aid!r}: register it on "
                "engine.adapters before submitting requests that name it")

    def _acquire_or_block(self, aid, blocked, private) -> Tuple:
        """The admission-side adapter gate, shared by the dense and
        paged paths: (ok, slot).  A refused acquire BLOCKS the lane for
        the rest of the burst (FIFO — later arrivals must not overtake a
        request waiting on a slot), exactly the page-refusal discipline."""
        if aid is None:
            return True, None
        aslot = self.adapters.acquire(aid)
        if aslot is None:
            blocked[private] = True
            return False, None
        return True, aslot

    def add_requests(self, reqs: List[Tuple]) -> List[bool]:
        """Admit a burst of (prompt, max_new_tokens, greedy, rid[, seed
        [, prefix[, adapter_id[, deadline_ms]]]]) requests (seed
        overrides rid in the sampling-key derivation; prefix is a
        shared preamble, COW page-shared on the paged path; adapter_id
        pins a registered per-user adapter slot for the request's
        lifetime; deadline_ms bounds its simulated clock).  Requests
        landing in the same lane share ONE packed B>1 prefill (the
        per-request prefill loop dominated burst admission wall time).
        Returns per-request admitted flags; soft-refused requests (lane
        full / free pages short / adapter slots all pinned) should be
        resubmitted later, hard rejects land in ``pop_rejected``."""
        if self.paged:
            return self._add_requests_paged(reqs)
        flags = [False] * len(reqs)
        jobs = {True: [], False: []}
        free = {True: self.edge_lane.free_slots(),
                False: self.cloud_lane.free_slots()}
        blocked = {True: False, False: False}
        for i, (prompt, max_new, greedy, rid, *rest) in enumerate(reqs):
            prefix = rest[1] if len(rest) > 1 else None
            aid = rest[2] if len(rest) > 2 else None
            deadline = rest[3] if len(rest) > 3 else None
            full = (prefix or "") + prompt
            private = self.detector.detect(full)
            if aid is not None and (self.adapters is None
                                    or not self.adapters.known(aid)):
                self._rejected.append((rid, self._adapter_reject_msg(aid)))
                continue
            if blocked[private] or not free[private]:
                continue
            ok, aslot = self._acquire_or_block(aid, blocked, private)
            if not ok:
                continue
            slot = free[private].pop(0)
            jobs[private].append((slot, full, max_new, greedy,
                                  rid, private,
                                  rest[0] if rest else None, aslot,
                                  deadline))
            flags[i] = True
        self.edge_lane.admit_many(jobs[True])
        self.cloud_lane.admit_many(jobs[False])
        return flags

    def _add_requests_paged(self, reqs: List[Tuple]) -> List[bool]:
        """Paged admission gate: free SLOT and free PAGES, per lane and
        per model.  Tokenization happens here (the gate needs page
        demands) and so does the page reservation — the prefill can
        then never run out of pool mid-burst.

        The LAZY demand (prompt pages + one decode page, capped at the
        worst case) is what gets reserved; the HARD-reject predicate
        stays the worst case ``ceil(min(len + max_new, max_ctx) /
        page_size)`` against TOTAL pool capacity, so any admitted row
        can always finish alone (the growth-time deadlock breaker
        relies on it).  Hard rejects land in ``pop_rejected`` naming
        the offending (model, demand, capacity); a soft refusal BLOCKS
        the lane for the rest of the burst — later arrivals must not
        overtake a waiting request (FIFO, no starvation), and a lane
        with pending evictions admits nothing external at all."""
        flags = [False] * len(reqs)
        jobs = {True: [], False: []}
        free = {True: self.edge_lane.free_slots(),
                False: self.cloud_lane.free_slots()}
        blocked = {True: bool(self.edge_lane._evictq),
                   False: bool(self.cloud_lane._evictq)}
        for i, (prompt, max_new, greedy, rid, *rest) in enumerate(reqs):
            seed = rest[0] if rest else None
            prefix = rest[1] if len(rest) > 1 else None
            aid = rest[2] if len(rest) > 2 else None
            deadline = rest[3] if len(rest) > 3 else None
            full = (prefix or "") + prompt
            private = self.detector.detect(full)
            lane = self.edge_lane if private else self.cloud_lane
            if aid is not None and (self.adapters is None
                                    or not self.adapters.known(aid)):
                self._rejected.append((rid, self._adapter_reject_msg(aid)))
                continue
            raw = TOK.encode(full + " ")
            cap_ids = self.max_ctx - max_new - 1
            ids = raw[:cap_ids]
            truncated = len(raw) > cap_ids
            alloc_len = min(len(ids) + max_new, self.max_ctx)
            cap_pages = PAG.pages_for(alloc_len, self.dep.page_size)
            entry = None
            if prefix and self.router is None and aid is None and \
                    len(ids) <= self.chunk_width:
                # COW sharing needs the tokenization to split cleanly at
                # the prefix boundary, an actual suffix to prefill, and
                # a prompt that fits the dense prefill buffer (longer
                # prompts go chunked, unshared — the chunk freeze owns
                # every page it writes); router-gated requests merge
                # per-request LoRA into the prefix KV, so they never
                # share
                entry = lane.ensure_prefix(prefix)
                if entry is not None and not (
                        len(ids) > entry["pre_len"]
                        and ids[:entry["pre_len"]] == entry["pre_ids"]):
                    entry = None
            share_np = entry["share_np"] if entry else 0
            worst_s = lane.pager_s.demand(alloc_len, share_np)
            worst_l = (0, 0)
            if lane.use_cloud:
                worst_l = lane.pager_l.demand(alloc_len, share_np)
            if not lane.pager_s.fits_pool(*worst_s):
                self._rejected.append((rid, (
                    f"slm page demand {worst_s[0]} exceeds pool "
                    f"capacity {lane.pager_s.alloc.num_pages} pages")))
                continue
            if lane.use_cloud and not lane.pager_l.fits_pool(*worst_l):
                self._rejected.append((rid, (
                    f"llm page demand {worst_l[0]} exceeds pool "
                    f"capacity {lane.pager_l.alloc.num_pages} pages")))
                continue
            if blocked[private]:
                continue                   # FIFO: no overtaking
            if self.lazy_pages:
                nf_s, nl_s = lane.pager_s.demand_lazy(
                    len(ids), alloc_len, share_np)
                nf_l, nl_l = (lane.pager_l.demand_lazy(
                    len(ids), alloc_len, share_np)
                    if lane.use_cloud else (0, 0))
            else:
                (nf_s, nl_s), (nf_l, nl_l) = worst_s, worst_l
            if not free[private] \
                    or not lane.pager_s.fits_free(nf_s, nl_s) or (
                        lane.use_cloud
                        and not lane.pager_l.fits_free(nf_l, nl_l)):
                blocked[private] = True    # soft: retry when pages free
                continue
            ok, aslot = self._acquire_or_block(aid, blocked, private)
            if not ok:                     # soft: retry when pins drop
                continue
            slot = free[private].pop(0)
            rows_s = lane.pager_s.admit(
                slot, nf_s, shared=entry["pids_s"] if entry else (),
                cap_pages=cap_pages)
            rows_l = None
            if rows_s is not None and lane.use_cloud:
                rows_l = lane.pager_l.admit(
                    slot, nf_l, shared=entry["pids_l"] if entry else (),
                    cap_pages=cap_pages)
                if rows_l is None:         # pragma: no cover (fits_free)
                    lane.pager_s.release(slot)
            if rows_s is None or (lane.use_cloud and rows_l is None):
                free[private].insert(0, slot)  # pragma: no cover
                blocked[private] = True        # pragma: no cover
                if aslot is not None:          # pragma: no cover
                    self.adapters.release(aslot)
                continue
            jobs[private].append(_PagedJob(
                slot, full, max_new, greedy, rid, private, seed, ids,
                rows_s, rows_l, entry, seq=self._next_seq(),
                truncated=truncated, aslot=aslot, deadline_ms=deadline))
            flags[i] = True
        self.edge_lane.admit_many(jobs[True])
        self.cloud_lane.admit_many(jobs[False])
        return flags

    def pop_rejected(self) -> List[Tuple[int, str]]:
        """Drain the hard-reject log: (rid, reason) for requests whose
        page demand can NEVER fit the pools (schedulers must error them
        out instead of retrying forever)."""
        out, self._rejected = self._rejected, []
        return out

    def resident_kv_bytes(self) -> int:
        """Bytes of KV state currently LIVE: allocated pages on the
        paged path (drops as rows drain and grows with actual lengths,
        with shared prefix pages counted once), the full allocated lane
        caches on the dense path (residency is B x max_seq regardless
        of occupancy — the tentpole's comparison point)."""
        total = 0
        for lane in (self.cloud_lane, self.edge_lane):
            if self.paged:
                for pager in (lane.pager_s, lane.pager_l):
                    if pager is not None:
                        total += pager.live_bytes(
                            pager.geo["page_bytes_full"],
                            pager.geo["page_bytes_local"])
            else:
                for c in (lane.s_cache, lane.l_cache):
                    if c is None:
                        continue
                    total += sum(
                        leaf.size * leaf.dtype.itemsize
                        for k, v in c.items() if k != "pos"
                        for leaf in jax.tree.leaves(v))
        return total

    def kv_pool_bytes(self) -> int:
        """Total KV capacity in bytes: pool pages on the paged path,
        the would-be dense lane allocation otherwise (computed from
        abstract shapes, so it's meaningful before first admission)."""
        total = 0
        for lane in (self.cloud_lane, self.edge_lane):
            models = [self.slm] + ([self.llm] if lane.use_cloud else [])
            if self.paged:
                for pager in (lane.pager_s, lane.pager_l):
                    if pager is not None:
                        total += (pager.alloc.num_pages
                                  * pager.geo["page_bytes_full"])
                        if pager.local_alloc is not None:
                            total += (pager.local_alloc.num_pages
                                      * pager.geo["page_bytes_local"])
            else:
                for lm in models:
                    abs_c = jax.eval_shape(
                        lambda lm=lm: lm.init_cache(lane.batch,
                                                    self.max_seq))
                    total += sum(
                        leaf.size * jnp.dtype(leaf.dtype).itemsize
                        for leaf in jax.tree.leaves(abs_c)
                        if leaf.ndim >= 3)
        return total

    def active_count(self) -> int:
        # evicted-but-unfinished requests count as active: they hold no
        # pages but the lane still owes them a completion
        return (self.cloud_lane.active + len(self.cloud_lane._evictq)
                + self.edge_lane.active + len(self.edge_lane._evictq))

    def dispatch_step(self):
        """Dispatch both lanes' macro-steps WITHOUT syncing (no-op on
        the ``macro_k=0`` per-token path, which is inherently
        host-synchronous).  Follow with admission work to overlap it
        with the in-flight decode, then ``collect_step()``."""
        if self.macro_k:
            self.edge_lane.macro_dispatch(self.macro_k)
            if self.spec_k:
                self.cloud_lane.spec_dispatch(
                    -(-self.macro_k // self.spec_k), self.spec_k)
            else:
                self.cloud_lane.macro_dispatch(self.macro_k)

    def collect_step(self) -> List[Tuple[int, str, GenStats]]:
        """Sync + replay the in-flight macro-steps (or, with
        ``macro_k=0``, run one legacy per-token step).  Returns the
        requests that finished."""
        if self.macro_k:
            return (self.edge_lane.macro_collect()
                    + (self.cloud_lane.spec_collect() if self.spec_k
                       else self.cloud_lane.macro_collect()))
        out = self.edge_lane.step()
        if self.spec_k:
            # per-token cadence, speculative cloud lane: ONE burst per
            # boundary (k tokens per LLM dispatch, one sync)
            self.cloud_lane.spec_dispatch(1, self.spec_k)
            return out + self.cloud_lane.spec_collect()
        return out + self.cloud_lane.step()

    def step(self) -> List[Tuple[int, str, GenStats]]:
        """Advance both lanes by one macro-step (``macro_k`` tokens per
        occupied row in a single dispatch + single host sync per lane;
        ``macro_k=0`` falls back to the per-token reference path).
        Returns the requests that finished."""
        self.dispatch_step()
        return self.collect_step()


class SoloEngine:
    """Single-model greedy decoding (SLM-only / LLM-only baselines)."""

    def __init__(self, lm=None, params=None, expert_bank=None,
                 router: Optional[Router] = None, max_seq: int = 96,
                 deployment: Optional[ServingDeployment] = None):
        if deployment is None:
            deployment = ServingDeployment(lm, params,
                                           expert_bank=expert_bank,
                                           max_seq=max_seq)
        else:
            _reject_deployment_args(lm=(lm, None), params=(params, None),
                                    expert_bank=(expert_bank, None),
                                    max_seq=(max_seq, 96))
        self.dep = deployment
        self.lm, self.params = deployment.slm, deployment.slm_params
        self.bank, self.router = deployment.bank, router
        self.max_seq = deployment.max_seq
        self.adapters = (deployment.make_adapter_cache()
                         if deployment.adapter_slots else None)
        if self.bank is not None and router is None:
            raise ValueError(_BANK_NEEDS_GATING)
        if self.bank is not None and self.adapters is not None:
            raise ValueError(
                "router-gated expert bank and per-user adapter slots "
                "are mutually exclusive")
        self._lora = (deployment.lora
                      if router is not None and self.bank is not None
                      else None)
        # whether the LAST generate() call had to cut its prompt
        self.last_truncated = False

    @property
    def lora(self):
        if self.adapters is not None:
            return LORA.bank_for_model(self.adapters.bank)
        return self._lora

    def adapter_stats(self) -> Dict[str, int]:
        return self.adapters.stats() if self.adapters is not None else {}

    def generate(self, prompt: str, max_new_tokens: int = 16,
                 adapter_id: Optional[Any] = None) -> str:
        dep = self.dep
        gates = None
        lora = None
        aslot = None
        if adapter_id is not None:
            if self.adapters is None:
                raise ValueError(
                    "adapter_id= needs a deployment built with "
                    "adapter_slots=")
            aslot = self.adapters.acquire(adapter_id)
            if aslot is None:   # pragma: no cover (B=1 releases)
                raise RuntimeError("no adapter slot free")
            gates = jnp.asarray(
                LORA.slot_gates([aslot], self.adapters.num_slots))
            lora = self.lora
        elif self.router is not None and self.bank is not None:
            gates = jnp.asarray(self.router.gate_weights(prompt))[None, :]
            lora = self.lora
        raw = TOK.encode(prompt + " ")
        cap = self.max_seq - max_new_tokens - 1
        self.last_truncated = len(raw) > cap
        ids = raw[:cap]
        toks = jnp.asarray([ids], jnp.int32)
        logits, cache = dep.slm_prefill(self.params, toks, lora, gates)
        out: List[int] = []
        cur = logits[:, 0]
        for _ in range(max_new_tokens):
            nxt = int(jnp.argmax(cur[0]))
            out.append(nxt)
            if nxt == TOK.EOS:
                break
            logits, cache = dep.slm_decode(self.params, cache,
                                           jnp.asarray([[nxt]], jnp.int32),
                                           lora, gates)
            cur = logits[:, 0]
        if aslot is not None:
            self.adapters.release(aslot)
        return TOK.decode(out)
