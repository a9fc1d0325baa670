"""Speculative decode through the Floe pair (ISSUE 10 tentpole).

``BatchedHybridEngine(spec_k=K)`` lets the SLM draft K tokens
autoregressively (greedy over its OWN logits), then verifies the whole
window with ONE batched LLM dispatch; a fused accept/rollback epilogue
(``kernels/logit_fusion/ops.accept_prefix``) keeps the longest draft
prefix the fused distribution agrees with and rolls rejected SLM KV /
ring writes / paged positions back.  The contracts under test:

  (a) spec_k=0 is the untouched oracle, and under greedy CALM weather
      every spec_k emits BIT-IDENTICAL text/tokens/cloud telemetry to
      it — with strictly fewer LLM verify dispatches (counted on the
      deployment entry point, not inferred), per-token and macro,
      plain 2b and gemma3-ring, dense and paged;
  (b) when the fused choice DIVERGES from the draft (forced via a
      deterministic ``fuse_batched`` stub, the test_macro_step idiom)
      the rollback path re-reconciles exactly: same bits, rejected
      drafts rolled back, greedy and seeded;
  (c) after a full run the spec lane's dense KV caches are bitwise
      what a never-drafted run leaves behind, and paged pools drain to
      pristine;
  (d) breaker-degraded rows fall back to pure SLM drafting at zero
      cloud cost and the whole fault replay stays self-deterministic;
  (e) the swept-but-unwired ``moe_lora_delta_slots`` kernel now
      carries the adapter decode hot path under ``use_slot_kernel``
      with token-parity against the dense einsum gates (ISSUE 10
      satellite), composed with speculation;
  (f) spec_k validates against the drafter's ring window, and the
      mesh path (8 fake devices, subprocess on single-device tier-1)
      reproduces the single-device stream.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fusion as FUS
from repro.core import lora as LORA
from repro.models.model import LM
from repro.serving.deployment import ServingDeployment
from repro.serving.engine import BatchedHybridEngine
from repro.serving.latency import FaultModel, LatencyModel
from repro.serving.scheduler import (ContinuousBatchScheduler,
                                     summarize)

MULTI = len(jax.devices()) >= 4
multi = pytest.mark.skipif(
    not MULTI, reason="needs a >=4-device backend "
    "(--xla_force_host_platform_device_count; see the mesh-8 CI entry)")

PROMPTS = [
    "math: 12 plus 7 =",
    "my ssn is 123-45-6789",     # private -> edge lane
    "translate: water ->",
    "my doctor said rest",       # private -> edge lane
    "sort: 40 12 77 31 ->",
    "explain rainbows",
]
# CALM weather: every reply beats the deadline, so the burst's single
# per-burst arrival draw equals the per-token draws it replaces and the
# reconciliation is EXACT (see docs/serving.md "speculative decode")
CALM = dict(rtt_ms=50.0, jitter_ms=5.0, cloud_compute_ms=20.0, seed=7)
# faulty weather that degrades a k=2 burst by construction: weather is
# drawn once per burst, keyed at its first step, and bursts advance at
# most k steps, so a window of outage_len >= breaker_n * k steps holds
# breaker_n consecutive burst keys and trips the row; the first full
# window starts by step outage_period - 1, and the burst after the trip
# starts at most (breaker_n + 1) * k - 1 steps into it
CHAOS = dict(loss_rate=0.25, outage_period=5, outage_len=4, seed=3,
             breaker_n=2, breaker_m=3)
N_TOK = 10


def _build(gemma):
    if gemma:
        scfg = get_config("floe-slm-gemma3").reduced()
        slm = LM(scfg, remat=False, ring_cache=True)
    else:
        scfg = get_config("floe-slm-2b").reduced()
        slm = LM(scfg, remat=False)
    lcfg = get_config("floe-llm-7b").reduced()
    llm = LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


def _dep(parts, fault=None, **kw):
    slm, sp, llm, lp, mlp = parts
    return ServingDeployment(slm, sp, llm, lp, mlp,
                             latency=LatencyModel(**CALM),
                             timeout_ms=200.0, max_seq=48,
                             fault=fault, **kw)


def _skew_fusion(mlp, sl, ll, arrived):
    """Deterministic pure function of the logits whose greedy choice
    sometimes diverges from argmax(sl): the reduced random pair agrees
    on every position naturally, so without this stub the reject /
    rollback / correction path would never run.  Installed on the
    SHARED deployment before anything traces, both the per-token
    baseline and the burst verify see bitwise the same fused
    distribution — exactly the reconciliation contract."""
    v = sl.shape[-1]
    h = (jnp.sum(jnp.abs(sl) * 1e3, -1).astype(jnp.int32) % 3)
    top = jnp.argmax(sl, -1)
    choice = jnp.where(h == 0, (top + 7) % v, top)
    return jax.nn.one_hot(choice, v), jnp.ones((sl.shape[0],))


@pytest.fixture(scope="module")
def parts():
    return _build(False)


@pytest.fixture(scope="module")
def gemma_parts():
    return _build(True)


@pytest.fixture(scope="module")
def dep(parts):
    return _dep(parts)


@pytest.fixture(scope="module")
def gemma_dep(gemma_parts):
    return _dep(gemma_parts)


@pytest.fixture(scope="module")
def skew_dep(parts):
    d = _dep(parts)
    d.fuse_batched = _skew_fusion
    return d


@pytest.fixture(scope="module")
def gemma_skew_dep(gemma_parts):
    d = _dep(gemma_parts)
    d.fuse_batched = _skew_fusion
    return d


def _run(dep, spec_k, macro_k, *, paged=True, n_tok=N_TOK, seeded=False,
         count=False, **kw):
    eng = BatchedHybridEngine(deployment=dep, batch_size=4,
                              edge_batch_size=2, macro_k=macro_k,
                              paged=paged, spec_k=spec_k, **kw)
    calls = [0]
    if count:
        orig = dep.spec_cloud

        def counted(*a, **k):
            calls[0] += 1
            return orig(*a, **k)

        dep.spec_cloud = counted
    try:
        sched = ContinuousBatchScheduler(eng)
        for i, p in enumerate(PROMPTS):
            sched.submit(p, n_tok, greedy=not seeded,
                         seed=1000 + i if seeded else None)
        res = sched.run()
    finally:
        if count:
            dep.spec_cloud = orig
    return (res, calls[0], eng) if count else res


def _assert_reconciled(base, spec):
    """The spec run must emit the per-token oracle's stream bit for
    bit.  latency_ms/clock_ms are NOT compared: a burst legitimately
    charges one verify RTT + (n-1) edge-only steps."""
    assert [r.rid for r in spec] == [r.rid for r in base]
    for a, b in zip(base, spec):
        assert a.text == b.text, (a.rid, a.text, b.text)
        assert a.status is b.status
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.fusion_w == b.stats.fusion_w, a.rid


# --------------------------------------------- greedy reconciliation (a)


@pytest.mark.parametrize("pair", ["2b", "gemma"])
@pytest.mark.parametrize("macro_k", [0, 8])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_matches_per_token_oracle(request, pair, macro_k, k):
    d = request.getfixturevalue("dep" if pair == "2b" else "gemma_dep")
    base, base_calls, _ = _run(d, 0, macro_k, count=True)
    assert base_calls == 0                 # oracle never takes the path
    spec, calls, _ = _run(d, k, macro_k, count=True)
    _assert_reconciled(base, spec)
    # dispatch discipline, counted per request: the seed token rides
    # the prefill logits for free, so a row joins at most
    # ceil((tokens - 1) / k) verify bursts (+1 cloud_call for the
    # seed's prefill round-trip); the exact lane-level count is locked
    # by test_spec_dispatch_discipline below
    assert calls > 0
    cloud_reqs = [r for r in base if r.stats.cloud_tokens > 0]
    for r in spec:
        if r.stats.cloud_tokens > 0:
            assert r.stats.cloud_calls <= \
                1 + -(-(r.stats.tokens - 1) // k)
    base_tok_calls = sum(r.stats.cloud_calls for r in cloud_reqs)
    assert base_tok_calls == sum(r.stats.tokens for r in cloud_reqs)
    spec_calls = sum(r.stats.cloud_calls for r in spec)
    if k == 1:
        assert spec_calls <= base_tok_calls
    else:
        assert spec_calls < base_tok_calls       # strictly fewer
    # telemetry: drafts happened, acceptance can't exceed drafting,
    # and the oracle reports none
    drafted = sum(r.stats.spec_drafted for r in spec)
    accepted = sum(r.stats.spec_accepted for r in spec)
    assert drafted > 0 and 0 < accepted <= drafted
    assert all(r.stats.spec_drafted == 0 for r in base)
    s = summarize(spec)
    assert s["accept_rate"] == pytest.approx(accepted / drafted)
    assert s["cloud_calls_per_token"] < 1.0 or k == 1


@pytest.mark.timeout(540)
def test_spec_dispatch_discipline(dep):
    """PR 4-style dispatch counting on the live engine: 4 cloud rows
    x 9 tokens at k=4 pay the seed token (free — it rides the prefill
    logits) plus exactly ceil(8/4) = 2 verify bursts: 2 ``spec_cloud``
    dispatches, 2 host syncs, ZERO Python-level ``llm_decode`` calls.
    The per-token oracle pays one LLM dispatch per token after the
    prefill-fused first one (8)."""
    k, n_tok = 4, 9

    def drive(spec_k):
        eng = BatchedHybridEngine(deployment=dep, batch_size=4,
                                  edge_batch_size=2, macro_k=0,
                                  spec_k=spec_k)
        cloud = [p for p in PROMPTS if not eng.detector.detect(p)][:4]
        for i, p in enumerate(cloud):     # warmup: trace the burst jit
            assert eng.add_request(p, n_tok, True, i)
        while eng.active_count():
            eng.step()
        counts = {"spec": 0, "sync": 0, "llm": 0}

        def wrap(fn, key):
            def g(*a, **kw):
                counts[key] += 1
                return fn(*a, **kw)
            return g

        saved = {n: getattr(dep, n)
                 for n in ("spec_cloud", "fetch_traces", "llm_decode")}
        dep.spec_cloud = wrap(saved["spec_cloud"], "spec")
        dep.fetch_traces = wrap(saved["fetch_traces"], "sync")
        dep.llm_decode = wrap(saved["llm_decode"], "llm")
        try:
            for i, p in enumerate(cloud):
                assert eng.add_request(p, n_tok, True, 100 + i)
            while eng.active_count():
                eng.step()
        finally:
            for n, fn in saved.items():
                setattr(dep, n, fn)
        return counts

    spec = drive(k)
    assert spec["spec"] == -(-(n_tok - 1) // k) == 2
    assert spec["sync"] == spec["spec"]
    assert spec["llm"] == 0, "verify must be the ONLY LLM entry point"
    base = drive(0)
    assert base["spec"] == 0 and base["llm"] == n_tok - 1
    # headline: >= 1.5x fewer LLM round-trips at k=4 (here 4x)
    assert base["llm"] >= 1.5 * spec["spec"]


# -------------------------------------- forced divergence + rollback (b)


@pytest.mark.parametrize("pair", ["2b", "gemma"])
@pytest.mark.parametrize("k,seeded", [(2, False), (4, False), (4, True)])
def test_divergent_fusion_rolls_back_and_reconciles(request, pair, k,
                                                    seeded):
    d = request.getfixturevalue(
        "skew_dep" if pair == "2b" else "gemma_skew_dep")
    for macro_k in (0, 8):
        base = _run(d, 0, macro_k, seeded=seeded)
        spec = _run(d, k, macro_k, seeded=seeded)
        _assert_reconciled(base, spec)
        drafted = sum(r.stats.spec_drafted for r in spec)
        accepted = sum(r.stats.spec_accepted for r in spec)
        # the stub really forces rejections: some drafts were rolled
        # back, so the run exercised the restore + correction path
        assert 0 < accepted < drafted


def test_rollback_leaves_state_as_never_drafted(skew_dep):
    """After a full run with forced rejections the spec lane's DENSE
    caches must be bitwise what the per-token oracle leaves behind:
    every rejected draft's SLM KV write (and the verify writes past
    the accepted prefix) was rolled back, not just ignored.  On the
    paged path both engines must drain their pools to pristine."""
    base = _run(skew_dep, 0, 0, paged=False)
    b_eng = BatchedHybridEngine(deployment=skew_dep, batch_size=4,
                                edge_batch_size=2, macro_k=0,
                                paged=False, spec_k=0)
    s_eng = BatchedHybridEngine(deployment=skew_dep, batch_size=4,
                                edge_batch_size=2, macro_k=0,
                                paged=False, spec_k=4)
    for eng in (b_eng, s_eng):
        sched = ContinuousBatchScheduler(eng)
        for p in PROMPTS:
            sched.submit(p, N_TOK)
        res = sched.run()
        _assert_reconciled(base, res)

    def trees_equal(a, b, what):
        la = jax.tree.leaves(a)
        lb = jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=what)

    trees_equal(b_eng.cloud_lane.s_cache, s_eng.cloud_lane.s_cache,
                "SLM lane KV diverged from the never-drafted run")
    trees_equal(b_eng.cloud_lane.l_cache, s_eng.cloud_lane.l_cache,
                "LLM lane KV diverged from the never-drafted run")
    # paged variant: pools drain to pristine on both sides
    _, _, p_eng = _run(skew_dep, 4, 0, count=True)
    for pager in (p_eng.cloud_lane.pager_s, p_eng.cloud_lane.pager_l):
        if pager is None:
            continue
        pager.alloc.check()
        assert pager.alloc.live_pages == 0
        assert pager.alloc.free_pages == pager.alloc.num_pages


# ------------------------------------------- faults: degraded bursts (d)


def test_spec_under_faults_degrades_to_pure_slm(parts):
    d = _dep(parts, fault=FaultModel(**CHAOS))
    k = 2
    assert CHAOS["outage_len"] >= CHAOS["breaker_n"] * k
    assert N_TOK >= CHAOS["outage_period"] + (CHAOS["breaker_n"] + 1) * k - 1
    a = _run(d, k, 8)
    b = _run(d, k, 8)
    for ra, rb in zip(a, b):               # burst replay is a pure
        assert ra.text == rb.text          # function of (rid, step)
        assert ra.stats.latency_ms == rb.stats.latency_ms
        assert ra.stats.degraded_tokens == rb.stats.degraded_tokens
        assert ra.stats.cloud_calls == rb.stats.cloud_calls
    assert sum(r.stats.degraded_tokens for r in a) >= 1
    assert sum(r.stats.fallback_tokens for r in a) >= 1
    for r in a:
        # zero cloud cost while the breaker is open: a degraded burst
        # emits pure-SLM drafts without dispatching (cloud_calls only
        # counts attempted round-trips, one per non-degraded burst),
        # so calls + degraded tokens can never exceed the row's tokens
        assert r.stats.cloud_calls + r.stats.degraded_tokens <= \
            r.stats.tokens
    assert all(r.stats.tokens > 0 for r in a)


# --------------------------------------------- slot-kernel satellite (e)


def _mk_adapters(slm, names, rank=2, scale=0.5):
    """Randomized-B adapters (init_adapter zero-inits B, which would
    make the slot-kernel parity vacuous)."""
    out = {}
    for j, name in enumerate(names):
        ad = LORA.init_adapter(slm, jax.random.key(100 + j), rank=rank)
        body = {k: v for k, v in ad.items() if k != "_rank"}
        flat, treedef = jax.tree_util.tree_flatten_with_path(body)
        key = jax.random.key(500 + j)
        leaves = []
        for i, (path, leaf) in enumerate(flat):
            if path[-1].key == "B":
                leaf = (jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape) * scale
                        ).astype(leaf.dtype)
            leaves.append(leaf)
        body = jax.tree_util.tree_unflatten(treedef, leaves)
        body["_rank"] = ad["_rank"]
        out[name] = body
    return out


AID_OF = ["u0", None, "u1", "u2", "u0", None]


@pytest.mark.parametrize("pair", ["2b", "gemma"])
def test_slot_kernel_decode_parity(request, pair):
    """The scalar-prefetch ``moe_lora_delta_slots`` kernel carries the
    adapter decode hot path under ``use_slot_kernel=True`` and must
    reproduce the dense one-hot einsum gates token for token — per
    token, macro, and composed with spec_k drafting."""
    parts = request.getfixturevalue(
        "parts" if pair == "2b" else "gemma_parts")
    slm = parts[0]
    d = _dep(parts, adapter_slots=3)
    adapters = _mk_adapters(slm, ["u0", "u1", "u2"])

    def run(macro_k, use_slot, spec_k=0):
        eng = BatchedHybridEngine(deployment=d, batch_size=4,
                                  edge_batch_size=2, macro_k=macro_k,
                                  spec_k=spec_k,
                                  use_slot_kernel=use_slot)
        for name, ad in adapters.items():
            eng.adapters.register(name, ad)
        sched = ContinuousBatchScheduler(eng)
        for i, p in enumerate(PROMPTS):
            sched.submit(p, 6, greedy=(i % 2 == 0), seed=i,
                         adapter_id=AID_OF[i])
        out = {r.rid: r.text for r in sched.run()}
        assert eng.adapter_stats()["pinned"] == 0
        return out

    for macro_k in (0, 4):
        ref = run(macro_k, False)
        assert run(macro_k, True) == ref
        assert run(macro_k, True, spec_k=2) == ref


# ------------------------------------------------------- validation (f)


def test_spec_k_validates_against_ring_window(gemma_parts, parts):
    slm, sp, llm, lp, mlp = gemma_parts
    window = slm._ring_local_len(48)
    assert window > 0
    with pytest.raises(ValueError, match="ring window"):
        BatchedHybridEngine(slm, sp, llm, lp, mlp, max_seq=48,
                            latency=LatencyModel(**CALM),
                            spec_k=window + 1)
    with pytest.raises(ValueError, match="spec_k"):
        BatchedHybridEngine(*parts, max_seq=48,
                            latency=LatencyModel(**CALM), spec_k=-1)


# ------------------------------------------------------------------ mesh


def _spec_mesh_check():
    from repro.launch.mesh import make_serving_mesh
    assert len(jax.devices()) >= 4, "set XLA_FLAGS before running"
    mesh = make_serving_mesh(min(len(jax.devices()), 8))
    parts = _build(False)
    slm, sp, llm, lp, mlp = parts
    d = ServingDeployment(slm, sp, llm, lp, mlp,
                          latency=LatencyModel(**CALM),
                          timeout_ms=200.0, max_seq=48,
                          mesh=mesh, rules="inference")
    base = _run(d, 0, 0, n_tok=6)
    spec, calls, _ = _run(d, 2, 4, n_tok=6, count=True)
    assert [r.rid for r in spec] == [r.rid for r in base]
    for a, b in zip(base, spec):
        assert a.text == b.text, (a.rid, a.text, b.text)
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
    assert 0 < calls
    assert sum(r.stats.spec_drafted for r in spec) > 0
    print("SPEC-MESH-OK")


@multi
def test_spec_mesh_inprocess():
    _spec_mesh_check()


@pytest.mark.skipif(MULTI, reason="runs in-process on a multi-device "
                    "backend via test_spec_mesh_inprocess")
def test_spec_mesh_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, __file__], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"\n--- stdout\n{out.stdout}" \
                                f"\n--- stderr\n{out.stderr}"
    assert "SPEC-MESH-OK" in out.stdout


if __name__ == "__main__":
    _spec_mesh_check()
