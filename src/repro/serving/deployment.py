"""ServingDeployment — the placement layer of the Floe serving stack.

One object owns every decision about WHERE serving state lives and HOW
the compiled entry points see it; the engines (serving/engine.py) are
pure request bookkeeping on top.

  * the serving mesh (launch/mesh.py ``make_serving_mesh``) and the rule
    set (``launch/sharding.py RULESETS``: "inference" — weight-stationary
    decode, params replicated over ("pod", "data") and sharded over
    "model" — or "fsdp");
  * per-leaf param NamedShardings for the SLM, the LLM, the LoRA expert
    bank and the alignment MLP, built from the models' declarative axes
    trees (``LM.param_specs``) through ``param_shardings``; params are
    ``device_put`` onto the mesh at construction and NEVER gathered —
    per-device param bytes drop ~Nx on an N-way "model" axis
    (``per_device_param_bytes`` measures it from the live shards);
  * the lane-cache shardings (``lane_leaf_spec`` driven by the
    structural ``cache_batch_axes`` discovery) and the lane commit /
    constrain helpers the continuous-decode lanes use;
  * the jitted entry points — B=1 prefill, packed B>1 prefill, the
    per-token decode step, the K-token macro-step scan, and the
    admission row-scatter ``shard_map`` — compiled once per deployment
    with explicit ``in_shardings`` pinning the param layouts (and
    replicated ``out_shardings`` on logits), shared by every engine
    constructed through the deployment.

REPLICATION CONTRACT (Alg. 2 edge/cloud split): whatever the param and
cache layouts, per-token logits always come back replicated — the
Sec. IV-C fusion (alignment MLP + Pallas ``logit_fusion`` kernel) and
the sampling epilogue run edge-side on full vocab rows.  Bit-exact
parity with a replicated single-device engine is part of the contract
and locked in by tests/test_deployment.py.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fusion as FUS
from repro.core import lora as LORA
from repro.data import tokenizer as TOK
from repro.kernels.logit_fusion import ops as OPS
from repro.launch import sharding as SH
from repro.models import attention as ATT
from repro.serving import paging as PAG
from repro.serving import latency as LAT
from repro.serving.latency import FaultModel, LatencyModel


def jit_entry(name: str, fn, **kw):
    """``jax.jit`` of ``fn`` under the entry point's own name: the
    program compiles to the XLA module ``jit_<name>``, so a profile
    names it without lining executions up with calls."""
    @functools.wraps(fn)
    def entry(*args, **kwargs):
        return fn(*args, **kwargs)
    entry.__name__ = entry.__qualname__ = name
    return jax.jit(entry, **kw)


def cache_batch_axes(lm, max_seq: int):
    """Per-leaf batch axis of a lane cache, found structurally: the
    axis whose extent tracks init_cache's batch argument (grouped
    layouts stack it behind the group dims).  -1 marks batch-free
    leaves (the scalar "pos", which the lane overrides per-row)."""
    c2 = jax.eval_shape(lambda: lm.init_cache(2, max_seq))
    c3 = jax.eval_shape(lambda: lm.init_cache(3, max_seq))

    def ax(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        return -1
    return jax.tree.map(ax, c2, c3)


def model_param_shardings(lm, mesh: Mesh, rules="inference"):
    """Per-leaf NamedShardings of ``lm``'s params on a serving mesh —
    the layout a deployment keeps them in.  Pass it to
    ``lm.init(key, shardings=...)`` to draw the params in place."""
    if isinstance(rules, str):
        rules = SH.RULESETS[rules]
    return SH.param_shardings(lm.param_axes(), lm.param_specs(), mesh,
                              rules)


def alignment_shardings(vocab: int, mesh: Mesh, rules="inference",
                        hidden: int = 64):
    """The alignment MLP's counterpart of ``model_param_shardings``."""
    if isinstance(rules, str):
        rules = SH.RULESETS[rules]
    return SH.param_shardings(None, FUS.alignment_spec(vocab, hidden),
                              mesh, rules)


def _tree_bytes(tree, per_device: bool) -> int:
    """Bytes a tree occupies; per_device reads the placed arrays'
    addressable shards (replicated leaves count full size)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if per_device and hasattr(leaf, "addressable_shards"):
            d = leaf.addressable_shards[0].data
            total += d.size * d.dtype.itemsize
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total


class ServingDeployment:
    """Placement + compiled entry points for one servable model set.

    ``slm`` is required; ``llm``/``alignment_mlp`` make the deployment
    hybrid-servable (HybridEngine / BatchedHybridEngine), a lone
    ``slm`` serves SoloEngine.  Without ``mesh`` everything is identity
    placement on the default device — the engines behave exactly as the
    pre-deployment code did."""

    def __init__(self, slm, slm_params, llm=None, llm_params=None,
                 alignment_mlp=None, expert_bank=None,
                 latency: Optional[LatencyModel] = None,
                 timeout_ms: float = 200.0, max_seq: int = 96,
                 sample_seed: int = 0, mesh: Optional[Mesh] = None,
                 rules="inference", block_b: int = 8,
                 page_size: int = 16, max_ctx: Optional[int] = None,
                 adapter_slots: int = 0,
                 adapter_rank: Optional[int] = None,
                 fault: Optional[FaultModel] = None):
        assert slm is not None, "a deployment needs at least one model"
        # paged lanes gather exactly table_width * page_size slots back
        # into the dense rowwise layout; requiring page-aligned max_seq
        # makes that extent EQUAL to the dense cache's, so the paged
        # attention reduction is the bitwise-same computation
        assert max_seq % page_size == 0, \
            f"max_seq={max_seq} must be a multiple of page_size={page_size}"
        # max_ctx > max_seq widens the PAGED context only: block tables
        # (and the decode gather extent) cover max_ctx positions while
        # the dense prefill buffer stays max_seq wide — prompts beyond
        # it stream through chunked prefill.  Default keeps the dense
        # and paged extents equal (the bit-exactness contract above).
        self.max_ctx = max_ctx or max_seq
        assert self.max_ctx % page_size == 0 and self.max_ctx >= max_seq, \
            f"max_ctx={self.max_ctx} must be a page-aligned >= max_seq"
        self.page_size = page_size
        self.slm, self.llm = slm, llm
        self.bank = expert_bank
        self.latency = latency or LatencyModel()
        # fault=None (or an all-zero FaultModel) keeps the deployment on
        # the fault-free oracle path: no fault draws are traced and the
        # macro carry's breaker state is a frozen pass-through
        self.fault = fault
        if fault is not None and fault.loss_rate <= 0.0 \
                and (fault.outage_period <= 0 or fault.outage_len <= 0):
            self.fault = None
        self.timeout_ms = timeout_ms
        self.max_seq = max_seq
        self.sample_seed = sample_seed
        self.block_b = block_b
        self.mesh = mesh
        if isinstance(rules, str):
            rules = SH.RULESETS[rules]
        self.rules = rules or SH.RULES_INFERENCE

        # ---- param placement: per-leaf NamedShardings from the models'
        # declarative axes trees; device_put commits the layout once, so
        # every jit below sees pre-placed params and never gathers them
        self.slm_param_shardings = self._model_shardings(slm)
        self.llm_param_shardings = self._model_shardings(llm)
        self.mlp_shardings = self._mlp_shardings(alignment_mlp)
        lora = (LORA.bank_for_model(expert_bank)
                if expert_bank is not None else None)
        self.lora_shardings = (
            SH.bank_shardings(lora, mesh, self.rules)
            if mesh is not None and lora is not None else None)
        self.slm_params = self._place(slm_params, self.slm_param_shardings)
        self.llm_params = self._place(llm_params, self.llm_param_shardings)
        self.mlp = self._place(alignment_mlp, self.mlp_shardings)
        self.lora = self._place(lora, self.lora_shardings)

        # ---- per-user adapter slot bank: a fixed E-slot device bank
        # serving a registry of N >> E adapters (serving/adapters.py).
        # Slots must be REPLICATED across the batch shards (any row
        # gathers any slot through its one-hot gates) with the wide
        # projection dims over "model" — slot_bank_shardings, NOT the
        # expert-parallel bank_shardings above.  write_adapter_slot is
        # the ONE compiled mutation path: it donates the bank, so the
        # AdapterCache owning it must replace its reference per write.
        self.adapter_slots = adapter_slots
        self.adapter_rank = (adapter_rank or slm.cfg.lora_rank_max) \
            if adapter_slots else 0
        self.adapter_bank_shardings = None
        self.write_adapter_slot = None
        if adapter_slots:
            abs_bank = jax.eval_shape(
                lambda: LORA.empty_bank(slm, adapter_slots,
                                        self.adapter_rank))
            if mesh is not None:
                self.adapter_bank_shardings = SH.slot_bank_shardings(
                    abs_bank, mesh, self.rules)
            kw: Dict[str, Any] = {}
            if self.adapter_bank_shardings is not None:
                kw = dict(
                    in_shardings=(self.adapter_bank_shardings, None,
                                  None),
                    out_shardings=self.adapter_bank_shardings)
            self.write_adapter_slot = jit_entry(
                "write_adapter_slot", LORA.write_slot, donate_argnums=(0,),
                **kw)

        # ---- lane-cache layout (structural batch-axis discovery)
        self.slm_axes = cache_batch_axes(slm, max_seq)
        self.llm_axes = cache_batch_axes(llm, max_seq) if llm else None
        # paged lane layout: pool leaves keep the dense leaf's batch-
        # axis index (now the page axis, sharded over ("pod","data")
        # with KV width over "model" by the same lane_leaf_spec rules);
        # block tables and per-row pos are replicated.  Attention (GQA)
        # cache layouts only.
        self.slm_paged_axes = (self._paged_axes(slm, self.slm_axes)
                               if self._pageable(slm) else None)
        self.llm_paged_axes = (self._paged_axes(llm, self.llm_axes)
                               if llm is not None and self._pageable(llm)
                               else None)

        # ---- compiled entry points (shared by every engine built on
        # this deployment).  The macro-step reads the fusion/latency/
        # decode callables through `self` at trace time, so tests can
        # stub e.g. `dep.fuse_batched` before the first dispatch.
        rep = (NamedSharding(mesh, P()) if mesh is not None else None)
        psh_s, psh_l = self.slm_param_shardings, self.llm_param_shardings

        def jit(name, fn, n_extra, params_shardings, out=None, **kw):
            """jit_entry with the params arg (position 0) pinned to its
            placed layout when a mesh is present; remaining args and
            outputs are unconstrained unless ``out`` pins them."""
            if mesh is None or params_shardings is None:
                return jit_entry(name, fn, **kw)
            return jit_entry(
                name, fn,
                in_shardings=(params_shardings,) + (None,) * n_extra,
                out_shardings=out, **kw)

        self.slm_prefill = jit(
            "slm_prefill",
            lambda p, toks, lora, g: slm.prefill(
                p, {"tokens": toks}, max_seq, lora=lora, gates=g),
            3, psh_s)
        self.slm_prefill_packed = jit(
            "slm_prefill_packed",
            lambda p, toks, lens, lora, g: self._lane_out(
                slm.prefill_packed(p, {"tokens": toks}, lens, max_seq,
                                   lora=lora, gates=g), self.slm_axes),
            4, psh_s, out=(rep, None) if mesh is not None else None)
        self.slm_decode = jit(
            "slm_decode",
            lambda p, c, t, lora, g: self._lane_out(
                slm.decode_step(p, c, t, lora, g),
                self._axes_like(c, "slm")),
            4, psh_s, out=(rep, None) if mesh is not None else None)
        self.insert_slm = self._make_insert("insert_slm", self.slm_axes)
        self.insert_row = jit_entry(
            "insert_row",
            lambda full, rows, src, dst: full.at[dst].set(rows[src]))
        if self._pageable(slm):
            self.slm_page_rows = jit_entry(
                "slm_page_rows",
                lambda c: slm.cache_to_page_rows(c, page_size, max_seq))
            self.insert_slm_paged = self._make_insert_paged(slm)
            self.insert_slm_prefix = self._make_insert_prefix(slm)
            self.slm_build_prefix = jit(
                "slm_build_prefix",
                lambda p, toks, lora, g: slm.build_prefix(
                    p, toks, lora=lora, gates=g),
                3, psh_s)
            self.slm_prefill_suffix = jit(
                "slm_prefill_suffix",
                lambda p, toks, lens, hist, lora, g, pre, share:
                    self._suffix_out(slm, p, toks, lens, hist, lora, g,
                                     pre, share),
                5, psh_s, static_argnums=(6, 7))
            # chunked long-prompt prefill: one dispatch per middle
            # chunk — suffix prefill + page freeze + history extension
            self.slm_prefill_chunk = jit(
                "slm_prefill_chunk",
                lambda p, toks, lens, hist, lora, g, pre:
                    self._chunk_out(slm, p, toks, lens, hist, lora, g,
                                    pre),
                5, psh_s, static_argnums=(6,))
        self.free_paged_rows = jit_entry("free_paged_rows",
                                         self._free_paged_rows_impl)
        # lazy-growth helpers: batched block-table page mapping and
        # row-pos park/unpark (pos = FREED_POS drops every paged write)
        self.grow_block_pages = jit_entry("grow_block_pages",
                                          self._grow_block_impl)
        self.set_row_pos = jit_entry(
            "set_row_pos",
            lambda c, idx, val: dict(
                c, pos=c["pos"].at[idx].set(val, mode="drop")))
        if llm is not None:
            self.llm_prefill = jit(
                "llm_prefill",
                lambda p, toks: llm.prefill(p, {"tokens": toks}, max_seq),
                1, psh_l)
            self.llm_prefill_packed = jit(
                "llm_prefill_packed",
                lambda p, toks, lens: self._lane_out(
                    llm.prefill_packed(p, {"tokens": toks}, lens, max_seq),
                    self.llm_axes),
                2, psh_l, out=(rep, None) if mesh is not None else None)
            self.llm_decode = jit(
                "llm_decode",
                lambda p, c, t: self._lane_out(
                    llm.decode_step(p, c, t), self._axes_like(c, "llm")),
                2, psh_l, out=(rep, None) if mesh is not None else None)
            self.insert_llm = self._make_insert("insert_llm", self.llm_axes)
            if self._pageable(llm):
                self.llm_page_rows = jit_entry(
                    "llm_page_rows",
                    lambda c: llm.cache_to_page_rows(c, page_size,
                                                     max_seq))
                self.insert_llm_paged = self._make_insert_paged(llm)
                self.insert_llm_prefix = self._make_insert_prefix(llm)
                self.llm_build_prefix = jit(
                    "llm_build_prefix",
                    lambda p, toks: llm.build_prefix(p, toks), 1, psh_l)
                self.llm_prefill_suffix = jit(
                    "llm_prefill_suffix",
                    lambda p, toks, lens, hist, pre, share:
                        self._suffix_out(llm, p, toks, lens, hist, None,
                                         None, pre, share),
                    3, psh_l, static_argnums=(4, 5))
                self.llm_prefill_chunk = jit(
                    "llm_prefill_chunk",
                    lambda p, toks, lens, hist, pre:
                        self._chunk_out(llm, p, toks, lens, hist, None,
                                        None, pre),
                    3, psh_l, static_argnums=(4,))

        # the fusion entry points take the alignment MLP as an argument
        # (like every param tree above): a closed-over array would be
        # baked into each program as a constant — 131 MB of w1 at the
        # pair's V = 256 000, once per program that fuses
        if alignment_mlp is not None:
            self.fuse = jit_entry("fuse", FUS.fused_distribution)
            self.fuse_batched = jit_entry(
                "fuse_batched",
                lambda mlp, sl, ll, arrived: FUS.fused_distribution_kernel(
                    mlp, sl, ll, arrived, block_b=block_b, mesh=mesh))
        self.softmax_batched = jit_entry(
            "softmax_batched",
            lambda sl: jax.nn.softmax(sl.astype(jnp.float32), -1))
        self.argmax_batched = jit_entry("argmax_batched",
                                        lambda p: jnp.argmax(p, -1))
        self.sample_batched = jit_entry(
            "sample_batched",
            lambda probs, rids, steps: OPS.sample_fused(
                probs, rids, steps, seed=self.sample_seed))
        # counter-based network weather, one vectorized draw per call:
        # lat_batched serves a whole batch row set (per-step AND inside
        # the macro scan — both see bitwise-identical weather),
        # lat_request a whole request's steps for the sequential engine
        self.lat_batched = jit_entry(
            "lat_batched",
            lambda rids, steps: self.latency.token_latency_device(
                self.timeout_ms, rids, steps))
        self.lat_request = jit_entry(
            "lat_request",
            lambda rid, steps: self.latency.token_latency_device(
                self.timeout_ms, jnp.full_like(steps, rid), steps))
        # counter-based fault weather, same parity discipline: one
        # vectorized (lost, outage) draw shared bitwise by the per-step
        # path, the macro scan and the sequential engine's prefetch
        if self.fault is not None:
            self.fault_batched = jit_entry(
                "fault_batched",
                lambda rids, steps: self.fault.faults_device(rids, steps))
            self.fault_request = jit_entry(
                "fault_request",
                lambda rid, steps: self.fault.faults_device(
                    jnp.full_like(steps, rid), steps))
        else:
            self.fault_batched = None
            self.fault_request = None
        # the macro-step trace fetch — an attribute so dispatch-
        # discipline tests can wrap it and count host syncs
        self.fetch_traces = jax.device_get
        if llm is not None:
            self.macro_cloud = self._make_macro(use_cloud=True)
            self.spec_cloud = self._make_spec()
        self.macro_edge = self._make_macro(use_cloud=False)

    # ------------------------------------------------------ param layout
    def _model_shardings(self, lm):
        if self.mesh is None or lm is None:
            return None
        return model_param_shardings(lm, self.mesh, self.rules)

    def _mlp_shardings(self, mlp):
        if self.mesh is None or mlp is None:
            return None
        return alignment_shardings(mlp["w1"].shape[0] // 2, self.mesh,
                                   self.rules, mlp["b1"].shape[0])

    def _place(self, tree, shardings):
        if tree is None or shardings is None:
            return tree
        return jax.device_put(tree, shardings)

    def per_device_param_bytes(self) -> Dict[str, int]:
        """Measured per-device bytes of the placed serving param state
        (addressable shard 0 of every leaf; replicated leaves count
        full size, exactly what a device must hold).  ``replicated_
        bytes`` is the no-mesh footprint for comparison — the Nx
        shrink on an N-way model axis is the tentpole's memory claim."""
        parts = {"slm": self.slm_params, "llm": self.llm_params,
                 "alignment_mlp": self.mlp, "lora_bank": self.lora}
        out: Dict[str, int] = {}
        total = rep = 0
        for name, tree in parts.items():
            if tree is None:
                continue
            b = _tree_bytes(tree, per_device=True)
            out[f"{name}_bytes"] = b
            total += b
            rep += _tree_bytes(tree, per_device=False)
        out["total_bytes"] = total
        out["replicated_bytes"] = rep
        return out

    # ------------------------------------------------------ adapter bank
    def init_adapter_bank(self):
        """A fresh all-zero slot bank, placed per the slot-bank rules.
        Every AdapterCache gets its OWN bank (``write_adapter_slot``
        donates its input, so two caches can never share a buffer)."""
        assert self.adapter_slots, \
            "deployment built without adapter_slots"
        bank = LORA.empty_bank(self.slm, self.adapter_slots,
                               self.adapter_rank)
        return self._place(bank, self.adapter_bank_shardings)

    def make_adapter_cache(self):
        """Host-side refcounted residency manager over a fresh slot
        bank, wired to the donating compiled write path."""
        from repro.serving.adapters import AdapterCache
        return AdapterCache(self.adapter_slots, self.init_adapter_bank(),
                            self.write_adapter_slot)

    # ------------------------------------------------------- lane layout
    def axes_for(self, lm):
        return self.slm_axes if lm is self.slm else self.llm_axes

    def lane_shardings(self, lm, batch: int) -> Any:
        """The NamedSharding tree a lane cache of ``lm`` is laid out
        with (None without a mesh) — the contract tests assert against
        ``leaf.sharding`` on the live lane caches."""
        if self.mesh is None:
            return None
        cache = jax.eval_shape(
            lambda: dict(lm.init_cache(batch, self.max_seq),
                         pos=jnp.zeros((batch,), jnp.int32)))
        return SH.lane_cache_shardings(cache, self.axes_for(lm),
                                       self.mesh, self.rules)

    def init_lane_cache(self, lm, batch: int) -> Any:
        """A freshly allocated stacked lane cache (per-row pos), laid
        out over the mesh per the launch/sharding.py lane rules."""
        cache = dict(lm.init_cache(batch, self.max_seq),
                     pos=jnp.zeros((batch,), jnp.int32))
        if self.mesh is None:
            return cache
        return jax.device_put(cache, SH.lane_cache_shardings(
            cache, self.axes_for(lm), self.mesh, self.rules))

    def commit_replicated(self, x):
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def constrain_lane(self, cache, axes_tree):
        return jax.tree.map(
            lambda x, ab: jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, SH.lane_leaf_spec(
                    x.shape, ab, self.mesh, self.rules))),
            cache, axes_tree)

    def replicated(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P()))

    def _lane_out(self, logits_and_cache, axes_tree):
        """Constrain a (logits, cache) pair to the lane layout: cache
        leaves to their per-leaf lane specs, logits replicated (the
        fusion replication contract).  Identity without a mesh."""
        logits, cache = logits_and_cache
        if self.mesh is None:
            return logits, cache
        return self.replicated(logits), self.constrain_lane(cache,
                                                            axes_tree)

    # ---------------------------------------------------- macro-step jit
    def _make_macro(self, use_cloud: bool):
        """Build the jitted K-token macro-step for one lane flavour.

        One dispatch decodes K tokens for the whole batch via an
        on-device ``lax.scan``: per-row counter-based latency draws,
        Pallas logit fusion with the arrived mask, the fused
        greedy-argmax / keyed-categorical epilogue, EOS + max_new done
        masks, row parking at FREED_POS, and both models' decode steps —
        carrying only device arrays between iterations.  The cloud LLM
        decode for step t+1 depends only on step t's selected token, not
        on the host consuming step t's trace, so XLA's async dispatch
        overlaps it with the fusion/epilogue of the next iteration and
        the host syncs exactly once per K tokens, on the stacked traces.

        Lane caches, current logits and the per-row circuit-breaker
        state are DONATED (argnums 5-10): the macro-step updates them in
        place, invalidating any stale references a caller may hold.
        ``k`` and ``sample`` (whether any row draws categorically) are
        static — at most two traces per lane flavour per K.  Param args
        are pinned to their placed layouts via ``in_shardings`` on a
        mesh deployment.

        With a ``FaultModel`` on a cloud lane the arrived mask extends
        from "arrived <= timeout" to "arrived AND not lost AND not in
        outage AND not breaker-degraded": lost/outage tokens fall back
        to the SLM distribution exactly like timeout tokens (and charge
        the full fallback latency — we waited for a reply that never
        came), while breaker-degraded rows decode SLM-only with no
        cloud wait charged.  The (fails, cooldown) hysteresis lives in
        the scan carry — never on the host — and the traces additionally
        record the per-token loss draw so the host mirror can replay the
        identical breaker recurrence from the trace alone (outages are a
        pure function of the step index, recomputed host-side)."""
        dep = self
        fault = self.fault if use_cloud else None

        def impl(slm_params, llm_params, mlp, lora, gates,
                 s_cache, l_cache, sl, ll, fails, cooldown,
                 rids, key_ids, steps, max_new, greedy, done,
                 k: int, sample: bool):
            b = sl.shape[0]

            def body(carry, _):
                s_cache, l_cache, sl, ll, fails, cooldown, steps, done \
                    = carry
                active = ~done
                new_fails, new_cooldown = fails, cooldown
                lost = jnp.zeros((b,), bool)
                if use_cloud:
                    lat, ok = dep.lat_batched(rids, steps)
                    if fault is not None:
                        lost, outage = dep.fault_batched(rids, steps)
                        raw = lost | outage
                        (new_fails, new_cooldown, degraded, _attempt,
                         fail, _trip, _recover) = \
                            LAT.breaker_transition_device(
                                fails, cooldown, active, raw,
                                fault.breaker_n, fault.breaker_m)
                        arrived = OPS.cloud_arrival_mask(
                            ok, active, lost, outage, degraded)
                        edge = jnp.float32(dep.latency.edge_compute_ms)
                        lat = jnp.where(
                            degraded, edge,
                            jnp.where(fail, jnp.maximum(
                                edge, jnp.float32(dep.timeout_ms)), lat))
                    else:
                        arrived = OPS.cloud_arrival_mask(ok, active)
                    probs, w = dep.fuse_batched(mlp, sl, ll, arrived)
                else:
                    probs = dep.softmax_batched(sl)
                    w = jnp.ones((b,), jnp.float32)
                    lat = jnp.zeros((b,), jnp.float32)
                    arrived = jnp.zeros((b,), bool)
                nxt = OPS.select_sample_fused(probs, greedy, key_ids,
                                              steps, seed=dep.sample_seed,
                                              sample=sample)
                done_now = active & ((nxt == TOK.EOS)
                                     | (steps + 1 >= max_new))
                feed = jnp.where(active & ~done_now, nxt, 0)[:, None]

                def park(c):
                    # rows that just finished: freeze before this very
                    # decode so their caches never see the dummy token
                    return dict(c, pos=jnp.where(done_now, ATT.FREED_POS,
                                                 c["pos"]))

                # inactive rows (parked-for-growth live rows, empty
                # slots, just-finished rows) keep their pending logits:
                # a parked row resumes from the SAME distribution at a
                # later boundary, bit-identical to an uninterrupted run
                keep = (done | done_now)[:, None]
                s_logits, new_s = dep.slm_decode(
                    slm_params, park(s_cache), feed, lora, gates)
                new_sl = jnp.where(keep, sl, s_logits[:, 0])
                if use_cloud:
                    l_logits, new_l = dep.llm_decode(
                        llm_params, park(l_cache), feed)
                    new_ll = jnp.where(keep, ll, l_logits[:, 0])
                else:
                    new_l, new_ll = l_cache, ll
                new_carry = (new_s, new_l, new_sl, new_ll,
                             new_fails, new_cooldown,
                             steps + active.astype(jnp.int32),
                             done | done_now)
                return new_carry, (nxt, arrived, lat, w, active, lost)

            def pin(carry):
                # pin the scan carry to the lane layout at BOTH ends:
                # GSPMD's carry unification may otherwise override the
                # in-body constraints (it resharded pos/sl over the
                # batch axes) and reshard every iteration
                if dep.mesh is None:
                    return carry
                s_c, l_c, sl_c, ll_c, bf, bc, st, dn = carry
                s_c = dep.constrain_lane(s_c, dep._axes_like(s_c, "slm"))
                sl_c = dep.replicated(sl_c)
                if use_cloud:
                    l_c = dep.constrain_lane(l_c,
                                             dep._axes_like(l_c, "llm"))
                    ll_c = dep.replicated(ll_c)
                return (s_c, l_c, sl_c, ll_c, bf, bc, st, dn)

            carry, traces = jax.lax.scan(
                body, pin((s_cache, l_cache, sl, ll, fails, cooldown,
                           steps, done)),
                None, length=k)
            return pin(carry), traces

        kw: Dict[str, Any] = {}
        if self.mesh is not None:
            psh_l = self.llm_param_shardings if use_cloud else None
            psh_m = self.mlp_shardings if use_cloud else None
            kw["in_shardings"] = ((self.slm_param_shardings, psh_l, psh_m)
                                  + (None,) * 14)
        # k/sample are positional statics: pjit rejects kwargs when
        # in_shardings is given, so the engine passes them by position
        return jit_entry("macro_cloud" if use_cloud else "macro_edge", impl,
                         static_argnums=(17, 18),
                         donate_argnums=(5, 6, 7, 8, 9, 10), **kw)

    # ------------------------------------------------ speculative burst
    def _make_spec(self):
        """Build the jitted speculative draft/verify/accept burst
        (tentpole PR 10): the SLM autoregressively drafts k tokens
        (greedy over its OWN logits, the ordinary masked decode step +
        KV writes), ONE chained LLM dispatch then scores all k draft
        positions for the whole lane batch, and the fused epilogue
        accepts the longest prefix where the fused distribution's
        choice equals the draft, rolling rejected KV/ring/page writes
        back via ``spec_snapshot``/``spec_restore``.  One call == ONE
        cloud round-trip: the k inner LLM decode steps live in a single
        device dispatch, so the simulated link is charged once per
        burst instead of once per token.

        Speculative state invariant (held between bursts): the SLM sits
        at depth p = prompt_len + emitted; ``sl`` is its logits for the
        next emit; the LLM sits ONE BEHIND at depth p-1 with the last
        emitted token pending in ``lt`` — the verify scan feeds
        [lt, d_0..d_{k-2}] so its k logit rows are the baseline cloud
        logits for emit positions steps+[0, k), making the fused
        distributions along the accepted prefix bitwise the per-token
        path's (greedy reconciliation contract; seeded sampling keys
        each position at steps+i exactly like the baseline).

        Network weather is drawn ONCE per burst, keyed by the burst's
        FIRST step (counter-based, order-independent); the breaker
        transition runs once per burst, and degraded / non-arrived rows
        fuse against w=1 — pure SLM drafting at zero cloud cost, which
        under greedy accepts the whole window (zero rollback).

        Same donation/sharding discipline as ``_make_macro``: caches,
        logits, ``lt`` and breaker state donated (argnums 5-10), params
        pinned, carry pinned to the lane layout at both ends.  Traces:
        (sels (k,B), n_emit, c_sel, arrived, lat, w (k,B), lost)."""
        dep = self
        fault = self.fault

        def impl(slm_params, llm_params, mlp, lora, gates,
                 s_cache, l_cache, sl, lt, fails, cooldown,
                 rids, key_ids, steps, max_new, greedy, done,
                 k: int, sample: bool):
            b = sl.shape[0]
            active = ~done
            pos_s0 = s_cache["pos"]
            pos_l0 = l_cache["pos"]
            snap_s = dep.slm.spec_snapshot(s_cache, pos_s0, k,
                                           dep.max_seq)
            snap_l = dep.llm.spec_snapshot(l_cache, pos_l0, k,
                                           dep.max_seq)

            def pin_s(c, cur):
                if dep.mesh is None:
                    return c, cur
                return (dep.constrain_lane(c, dep._axes_like(c, "slm")),
                        dep.replicated(cur))

            def pin_l(c):
                if dep.mesh is None:
                    return c
                return dep.constrain_lane(c, dep._axes_like(c, "llm"))

            # ---- draft: k masked SLM decode steps, greedy over the
            # SLM's own logits; inactive rows' writes drop at FREED_POS
            def dbody(carry, _):
                c, cur = carry
                d = jnp.argmax(cur, axis=-1).astype(jnp.int32)
                feed = jnp.where(active, d, 0)[:, None]
                logits, c = dep.slm_decode(slm_params, c, feed, lora,
                                           gates)
                return pin_s(c, logits[:, 0]), (cur, d)

            (s_c, sl_k), (sls, ds) = jax.lax.scan(
                dbody, pin_s(s_cache, sl), None, length=k)

            # ---- verify: ONE dispatch, k chained LLM decode steps over
            # [lt, d_0..d_{k-2}] — the one-behind protocol needs no
            # same-depth re-dispatch after a rejection
            feeds = jnp.concatenate([lt[None, :], ds[:-1]], axis=0)

            def vbody(c, tok):
                feed = jnp.where(active, tok, 0)[:, None]
                logits, c = dep.llm_decode(llm_params, c, feed)
                return pin_l(c), logits[:, 0]

            l_c, lls = jax.lax.scan(vbody, pin_l(l_cache), feeds)

            # ---- burst weather: one draw, keyed at the first step
            new_fails, new_cooldown = fails, cooldown
            lost = jnp.zeros((b,), bool)
            lat, ok = dep.lat_batched(rids, steps)
            if fault is not None:
                lost, outage = dep.fault_batched(rids, steps)
                raw = lost | outage
                (new_fails, new_cooldown, degraded, _attempt,
                 fail, _trip, _recover) = LAT.breaker_transition_device(
                    fails, cooldown, active, raw,
                    fault.breaker_n, fault.breaker_m)
                arrived = OPS.cloud_arrival_mask(ok, active, lost,
                                                 outage, degraded)
                edge = jnp.float32(dep.latency.edge_compute_ms)
                lat = jnp.where(
                    degraded, edge,
                    jnp.where(fail, jnp.maximum(
                        edge, jnp.float32(dep.timeout_ms)), lat))
            else:
                arrived = OPS.cloud_arrival_mask(ok, active)

            # ---- fused accept epilogue: position i fuses the baseline
            # pair (sls[i], lls[i]) and selects with the baseline key
            sels, ws = [], []
            for i in range(k):
                probs_i, w_i = dep.fuse_batched(mlp, sls[i], lls[i],
                                                arrived)
                sels.append(OPS.select_sample_fused(
                    probs_i, greedy, key_ids, steps + i,
                    seed=dep.sample_seed, sample=sample))
                ws.append(w_i)
            sels = jnp.stack(sels)
            w = jnp.stack(ws)
            n_emit, c_sel, done_now, correction = OPS.accept_prefix(
                ds, sels, steps, max_new, active, TOK.EOS)

            # ---- rollback: keep the accepted draft writes (the tokens
            # the baseline would have fed), restore the rest.  SLM:
            # done/correction rows never fed their last emitted token;
            # LLM (one behind): exactly n_emit feeds were baseline
            # (n_emit-1 <= c_sel always)
            keep_s = jnp.where(
                active, jnp.where(done_now | correction, n_emit - 1, k),
                k)
            keep_l = jnp.where(active, n_emit, k)
            s_c = dep.slm.spec_restore(s_c, snap_s, pos_s0, keep_s,
                                       dep.max_seq)
            l_c = dep.llm.spec_restore(l_c, snap_l, pos_l0, keep_l,
                                       dep.max_seq)

            # ---- correction decode: feed the diverged token to the
            # SLM only (the LLM stays one behind, it becomes lt)
            last_sel = jnp.take_along_axis(
                sels, jnp.maximum(n_emit - 1, 0)[None, :], axis=0)[0]
            s_c = dict(s_c, pos=jnp.where(correction,
                                          pos_s0 + n_emit - 1,
                                          ATT.FREED_POS))
            corr_logits, s_c = dep.slm_decode(
                slm_params, s_c,
                jnp.where(correction, last_sel, 0)[:, None], lora, gates)

            # ---- position fixup: ongoing rows advance n_emit, done
            # rows park at FREED_POS (the macro park discipline),
            # untouched rows keep their entry pos
            s_c = dict(s_c, pos=jnp.where(
                active & ~done_now, pos_s0 + n_emit,
                jnp.where(done_now, ATT.FREED_POS, pos_s0)))
            l_c = dict(l_c, pos=jnp.where(
                active & ~done_now, pos_l0 + n_emit,
                jnp.where(done_now, ATT.FREED_POS, pos_l0)))

            # ---- next-emit logits: full accept continues from the
            # draft chain's last logits; a correction row continues
            # from the just-decoded diverged token; a done row keeps
            # the logits that produced its final token (the macro
            # keep-pending discipline)
            sls_ext = jnp.concatenate([sls, sl_k[None]], axis=0)
            idx = jnp.where(done_now, jnp.maximum(n_emit - 1, 0), n_emit)
            cand = jnp.take_along_axis(
                sls_ext, idx[None, :, None], axis=0)[0]
            new_sl = jnp.where(correction[:, None], corr_logits[:, 0],
                               cand)
            new_sl = jnp.where(active[:, None], new_sl, sl)
            new_lt = jnp.where(active, last_sel, lt)
            if dep.mesh is not None:
                s_c = dep.constrain_lane(s_c, dep._axes_like(s_c, "slm"))
                l_c = dep.constrain_lane(l_c, dep._axes_like(l_c, "llm"))
                new_sl = dep.replicated(new_sl)
                new_lt = dep.replicated(new_lt)
            carry = (s_c, l_c, new_sl, new_lt, new_fails, new_cooldown,
                     steps + n_emit, done | done_now)
            return carry, (sels, n_emit, c_sel, arrived, lat, w, lost)

        kw: Dict[str, Any] = {}
        if self.mesh is not None:
            kw["in_shardings"] = ((self.slm_param_shardings,
                                   self.llm_param_shardings,
                                   self.mlp_shardings)
                                  + (None,) * 14)
        return jit_entry("spec_cloud", impl, static_argnums=(17, 18),
                         donate_argnums=(5, 6, 7, 8, 9, 10), **kw)

    # ------------------------------------------------- cache row scatter
    def _make_insert(self, name: str, axes_tree):
        """Jitted (full, row_cache, src_rows, dst_slots) scatter of
        prefilled cache rows into a stacked lane cache — ALL rows of an
        admission burst in one fused update (a per-row loop would copy
        the whole lane cache once per row), generic over the model's
        cache layout.  src/dst: (n,) int32 index arrays.

        With a mesh, batch-sharded leaves scatter through a
        ``shard_map`` over the batch mesh axes: each device holds only
        its own rows, translates dst slots to shard-local indices and
        drops rows owned by other shards, so admitting a burst never
        gathers the whole lane cache to one device (only the freshly
        prefilled rows — n of them — are broadcast)."""
        axes = jax.tree.leaves(axes_tree)
        mesh, rules = self.mesh, self.rules
        daxes = SH.batch_axes(mesh) if mesh is not None else ()
        sizes = dict(mesh.shape) if mesh is not None else {}

        def plain(f, r, ax, src, dst):
            taken = jnp.moveaxis(
                jnp.take(r, src, axis=ax), ax, 0).astype(f.dtype)
            fm = jnp.moveaxis(f, ax, 0).at[dst].set(taken)
            return jnp.moveaxis(fm, 0, ax)

        def sharded(f, r, ax, src, dst, spec):
            # batch moved to front; a dim d of the original layout lands
            # at d (d > ax), d + 1 (d < ax), or 0 (d == ax)
            taken = jnp.moveaxis(
                jnp.take(r, src, axis=ax), ax, 0).astype(f.dtype)
            fm = jnp.moveaxis(f, ax, 0)
            mspec = [None] * fm.ndim
            mspec[0] = spec[ax]
            for d in range(len(spec)):
                if d != ax and spec[d] is not None:
                    mspec[d if d > ax else d + 1] = spec[d]
            rspec = list(mspec)
            rspec[0] = None              # admitted rows: replicated batch

            def body(f_loc, t_loc, dst_loc):
                idx = jnp.int32(0)
                for a in daxes:
                    idx = idx * sizes[a] + jax.lax.axis_index(a)
                nb = f_loc.shape[0]
                start = idx * nb
                # slots outside this shard -> index nb, dropped by the
                # scatter (never wrap: dst - start can be negative)
                loc = jnp.where((dst_loc >= start) & (dst_loc < start + nb),
                                dst_loc - start, nb)
                return f_loc.at[loc].set(t_loc, mode="drop")

            fm = jax.shard_map(body, mesh=mesh,
                               in_specs=(P(*mspec), P(*rspec), P()),
                               out_specs=P(*mspec),
                               check_vma=False)(fm, taken, dst)
            return jnp.moveaxis(fm, 0, ax)

        def impl(full, row, src, dst):
            ff, fdef = jax.tree.flatten(full)
            rr, _ = jax.tree.flatten(row)
            out = []
            for f, r, ax in zip(ff, rr, axes):
                if f.ndim == 1:       # per-row pos <- scalar or (B,) row
                    out.append(f.at[dst].set(
                        jnp.reshape(r, (-1,))[src].astype(f.dtype)))
                    continue
                if mesh is None:
                    out.append(plain(f, r, ax, src, dst))
                    continue
                spec = SH.lane_leaf_spec(f.shape, ax, mesh, rules)
                if spec[ax] is None:  # batch replicated: plain scatter
                    res = jax.lax.with_sharding_constraint(
                        plain(f, r, ax, src, dst), NamedSharding(mesh, spec))
                else:
                    res = sharded(f, r, ax, src, dst, spec)
                out.append(res)
            return jax.tree.unflatten(fdef, out)
        return jit_entry(name, impl)

    # ------------------------------------------------------ paged layout
    # Paged lane caches keep the dense leaf tree with each (batch, seq)
    # prefix rewritten to (num_pages, page_size) plus replicated int32
    # "block" (B, nb) / "local" (B, nl) tables and per-row "pos".  The
    # pool's page axis sits at the dense batch-axis index, so the
    # launch/sharding lane_leaf_spec rules shard pages over
    # ("pod", "data") and the KV width over "model" unchanged.

    def _pageable(self, lm) -> bool:
        # GQA attention caches only: paging addresses (B, S, KV, hd)
        # leaves; SSM/hybrid/MLA state stays on the dense path
        return lm is not None and lm.cfg.family == "dense"

    def _paged_axes(self, lm, axes):
        abs_c = jax.eval_shape(lambda: lm.init_cache(1, self.max_seq))
        return PAG.paged_axes(abs_c, axes, self.max_seq)

    def paged_axes_for(self, lm):
        return (self.slm_paged_axes if lm is self.slm
                else self.llm_paged_axes)

    def _axes_like(self, cache, which: str):
        """The axis tree matching a live cache's structure — paged
        carries ("block" present) pick the paged tree, so one decode /
        macro jit serves both layouts by retrace."""
        if "block" in cache:
            return (self.slm_paged_axes if which == "slm"
                    else self.llm_paged_axes)
        return self.slm_axes if which == "slm" else self.llm_axes

    def paged_geometry(self, lm) -> Dict[str, int]:
        """Static page geometry of ``lm``'s cache: table widths and the
        bytes one page id costs across the whole leaf tree (pages span
        every layer, vLLM-style shared tables)."""
        abs_c = jax.eval_shape(lambda: lm.init_cache(1, self.max_seq))
        axes = self.axes_for(lm)
        ps, ms = self.page_size, self.max_seq
        local_len = PAG.local_seq_len(abs_c, axes, ms)
        return dict(
            nb=PAG.pages_for(self.max_ctx, ps),
            local_len=local_len,
            nl=PAG.pages_for(local_len, ps),
            page_bytes_full=PAG.page_bytes(abs_c, axes, ms, ps,
                                           local=False),
            page_bytes_local=PAG.page_bytes(abs_c, axes, ms, ps,
                                            local=True))

    def _paged_struct(self, lm, batch: int, pages: int,
                      local_pages: int):
        abs_c = jax.eval_shape(lambda: lm.init_cache(batch, self.max_seq))
        st = dict(PAG.pool_struct(abs_c, self.axes_for(lm), self.max_seq,
                                  self.page_size, pages, local_pages))
        geo = self.paged_geometry(lm)
        st["pos"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
        st["block"] = jax.ShapeDtypeStruct((batch, geo["nb"]), jnp.int32)
        if geo["nl"]:
            st["local"] = jax.ShapeDtypeStruct((batch, geo["nl"]),
                                               jnp.int32)
        return st

    def paged_lane_shardings(self, lm, batch: int, pages: int,
                             local_pages: int) -> Any:
        if self.mesh is None:
            return None
        st = self._paged_struct(lm, batch, pages, local_pages)
        return SH.lane_cache_shardings(st, self.paged_axes_for(lm),
                                       self.mesh, self.rules)

    def init_paged_lane_cache(self, lm, batch: int, pages: int,
                              local_pages: int) -> Any:
        """A fresh paged lane cache: zeroed pools, per-row pos, block /
        local tables filled with NO_PAGE (writes drop, gathers clamp
        onto masked garbage), placed per the lane sharding rules."""
        st = self._paged_struct(lm, batch, pages, local_pages)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), st)
        cache["block"] = jnp.full(st["block"].shape, PAG.NO_PAGE,
                                  jnp.int32)
        if "local" in st:
            cache["local"] = jnp.full(st["local"].shape, PAG.NO_PAGE,
                                      jnp.int32)
        if self.mesh is None:
            return cache
        return jax.device_put(cache, SH.lane_cache_shardings(
            st, self.paged_axes_for(lm), self.mesh, self.rules))

    def _free_paged_rows_impl(self, cache, idx):
        """Park drained rows AND unmap their pages: pos to FREED_POS,
        table rows to NO_PAGE, so subsequent in-scan writes drop and the
        freed page ids can be re-issued to a new admission without the
        old row ever touching them.  idx: (n,) int32 row slots."""
        out = dict(cache)
        out["pos"] = cache["pos"].at[idx].set(ATT.FREED_POS, mode="drop")
        out["block"] = cache["block"].at[idx].set(PAG.NO_PAGE,
                                                  mode="drop")
        if "local" in cache:
            out["local"] = cache["local"].at[idx].set(PAG.NO_PAGE,
                                                      mode="drop")
        return out

    def _suffix_out(self, lm, p, toks, lens, hist, lora, g,
                    pre_len: int, share_len: int):
        """Suffix prefill against a shared prefix history -> replicated
        last-token logits + per-row private page content (the
        insert_*_paged payload)."""
        logits, pc = lm.prefill_suffix(p, {"tokens": toks}, lens, hist,
                                       pre_len, lora=lora, gates=g)
        rows = lm.suffix_page_rows(hist, pc, lens, pre_len, share_len,
                                   self.page_size, self.max_seq)
        if self.mesh is not None:
            logits = self.replicated(logits)
        return logits, rows

    def _chunk_out(self, lm, p, toks, lens, hist, lora, g, pre_len: int):
        """One MIDDLE chunk of a chunked long-prompt prefill: suffix
        prefill against the history so far, page content over exactly
        this chunk's positions (share_len == pre_len — page-aligned
        chunk starts, so every page here is the row's own), and the
        extended history for the next chunk, in a single dispatch.
        ``toks`` must be exact-width (B=1, no padding)."""
        logits, pc = lm.prefill_suffix(p, {"tokens": toks}, lens, hist,
                                       pre_len, lora=lora, gates=g)
        rows = lm.suffix_page_rows(hist, pc, lens, pre_len, pre_len,
                                   self.page_size, self.max_seq)
        new_hist = lm.extend_history(hist, pc)
        if self.mesh is not None:
            logits = self.replicated(logits)
        return logits, rows, new_hist

    def _grow_block_impl(self, cache, rows, cols, pids):
        """Map freshly grown pages into live rows' block tables:
        ``block[rows[i], cols[i]] = pids[i]``.  Callers pad the update
        vectors to a power-of-two length with out-of-range row ids
        (mode="drop") so retraces stay bounded."""
        blk = cache["block"].at[rows, cols].set(pids, mode="drop")
        if self.mesh is not None:
            blk = self.replicated(blk)
        return dict(cache, block=blk)

    def _make_insert_paged(self, lm):
        """Jitted paged admission scatter.

        (full, rows, src, dst, dpf, dpl, block_rows, local_rows):
        ``rows`` is per-row PAGE content — ``cache_to_page_rows`` of a
        dense prefill (leaves (..., B, np, ps, KV, hd)) or a
        ``suffix_page_rows`` tree — with "pos" rows; ``src`` picks the
        admitted rows out of it and ``dst`` their lane slots.  ``dpf`` /
        ``dpl`` are (n, np) destination PAGE ids per admitted row
        (NO_PAGE-padded columns drop), ``block_rows`` / ``local_rows``
        the (n, nb) / (n, nl) table rows written at ``dst``.  Pool
        leaves rely on the trailing (..., B|P, np|ps, ...) layout, so
        one impl serves plain and grouped caches and both admission
        flavours (full-width nb vs suffix-width content) by retrace."""
        mesh, rules = self.mesh, self.rules
        ms = self.max_seq
        abs_c = jax.eval_shape(lambda: lm.init_cache(1, ms))
        abs_flat = jax.tree.leaves(dict(abs_c))

        def impl(full, rows, src, dst, dpf, dpl, block_rows, local_rows):
            core = {k: v for k, v in full.items()
                    if k not in ("block", "local")}
            ff, fdef = jax.tree.flatten(core)
            rr, _ = jax.tree.flatten(rows)
            out = []
            for f, r, ab in zip(ff, rr, abs_flat):
                if f.ndim == 1:          # per-row pos
                    out.append(f.at[dst].set(
                        jnp.reshape(r, (-1,))[src].astype(f.dtype)))
                    continue
                is_local = ab.shape[ab.ndim - 3] != ms
                dp = dpl if is_local else dpf
                # rows: (..., B, np, ps, KV, hd); pool: (..., P, ps, ...)
                taken = jnp.take(r, src, axis=r.ndim - 5).astype(f.dtype)
                tm = jnp.moveaxis(taken, (taken.ndim - 5, taken.ndim - 4),
                                  (0, 1))
                # explicit shape: zero-size leaves (empty group kinds)
                # make a -1 here ambiguous
                tm = tm.reshape((tm.shape[0] * tm.shape[1],)
                                + tm.shape[2:])
                pm = jnp.moveaxis(f, f.ndim - 4, 0)
                pm = pm.at[dp.reshape(-1)].set(tm, mode="drop")
                res = jnp.moveaxis(pm, 0, f.ndim - 4)
                if mesh is not None:
                    spec = SH.lane_leaf_spec(res.shape, res.ndim - 4,
                                             mesh, rules)
                    res = jax.lax.with_sharding_constraint(
                        res, NamedSharding(mesh, spec))
                out.append(res)
            new = dict(jax.tree.unflatten(fdef, out))
            rep = (lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P()))) if mesh is not None \
                else (lambda x: x)
            new["block"] = rep(full["block"].at[dst].set(
                block_rows, mode="drop"))
            if "local" in full:
                new["local"] = rep(full["local"].at[dst].set(
                    local_rows, mode="drop"))
            return new
        return jit_entry(
            "insert_slm_paged" if lm is self.slm else "insert_llm_paged",
            impl)

    def _make_insert_prefix(self, lm):
        """Jitted COW prefix-page write: (full, content, pids) scatters
        ``prefix_page_rows`` content (leaves (..., np, ps, KV, hd),
        batch squeezed) into pool pages ``pids`` (np,) — executed ONCE
        per registered prefix, then every sharing row just block-maps
        those pages.  Zero-page local leaves (rings are never shared)
        pass through."""
        mesh, rules = self.mesh, self.rules

        def scat(pool, rows, pids):
            if rows.shape[rows.ndim - 4] == 0:
                return pool
            rm = jnp.moveaxis(rows, rows.ndim - 4, 0).astype(pool.dtype)
            pm = jnp.moveaxis(pool, pool.ndim - 4, 0)
            pm = pm.at[pids].set(rm, mode="drop")
            res = jnp.moveaxis(pm, 0, pool.ndim - 4)
            if mesh is not None:
                spec = SH.lane_leaf_spec(res.shape, res.ndim - 4,
                                         mesh, rules)
                res = jax.lax.with_sharding_constraint(
                    res, NamedSharding(mesh, spec))
            return res

        def impl(full, content, pids):
            out = dict(full)
            if "k" in content:
                for n in ("k", "v"):
                    out[n] = scat(full[n], content[n], pids)
            else:
                for kind, kv in content.items():
                    out[kind] = dict(
                        full[kind],
                        **{n: scat(full[kind][n], kv[n], pids)
                           for n in ("k", "v")})
            return out
        return jit_entry(
            "insert_slm_prefix" if lm is self.slm else "insert_llm_prefix",
            impl)
