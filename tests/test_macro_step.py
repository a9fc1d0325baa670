"""Macro-step decode tests (ISSUE 4 tentpole).

The K-token macro-step (``BatchedHybridEngine(macro_k=K)``) must
  (a) keep the dispatch discipline: ONE jitted dispatch and ONE host
      sync per K tokens per lane — no per-token Python-level calls into
      the decode-path jits once the scan is traced;
  (b) stay bit-identical to the per-token reference path (``macro_k=0``)
      and to K=1, for greedy and seeded-sampling traffic, on both the
      plain and the gemma3 ring-cache layouts.

The mesh-sharded variant is covered by tests/test_sharded_lanes.py,
whose reference engine runs the legacy per-step path single-device
against the macro-step path on the mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fusion as FUS
from repro.models.model import LM
from repro.serving.engine import BatchedHybridEngine
from repro.serving.latency import LatencyModel
from repro.serving.scheduler import ContinuousBatchScheduler

PROMPTS = [
    "math: compute 12 plus 7 =",
    "my ssn is 123-45-6789, fill the benefits form",       # private
    "translate to french: water ->",
    "my doctor said my blood pressure is 140 over 90",     # private
    "sort ascending: 40 12 77 31 ->",
    "explain how rainbows form",
]
# jittery weather so rows genuinely mix arrived/fallback per step
JITTERY = dict(rtt_ms=160, jitter_ms=40.0, cloud_compute_ms=20, seed=7)


@pytest.fixture(scope="module")
def parts():
    scfg = get_config("floe-slm-2b").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm, llm = LM(scfg, remat=False), LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


@pytest.fixture(scope="module")
def gemma_parts():
    scfg = get_config("floe-slm-gemma3").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm = LM(scfg, remat=False, ring_cache=True)
    llm = LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


def _engine(parts, macro_k, latency_kw=JITTERY, flat_fusion=False, **kw):
    slm, sp, llm, lp, mlp = parts
    eng = BatchedHybridEngine(slm, sp, llm, lp, mlp, max_seq=48,
                              latency=LatencyModel(**latency_kw),
                              timeout_ms=200.0, batch_size=4,
                              edge_batch_size=2, macro_k=macro_k, **kw)
    if flat_fusion:
        v = slm.cfg.vocab_size
        eng.dep.fuse_batched = lambda mlp, sl, ll, arrived: (
            jnp.full((sl.shape[0], v), 1.0 / v),
            jnp.ones((sl.shape[0],)))
    return eng

def _run(parts, macro_k, n_tokens, greedy=True, seeded=False,
         flat_fusion=False):
    sched = ContinuousBatchScheduler(
        _engine(parts, macro_k, flat_fusion=flat_fusion))
    for i, p in enumerate(PROMPTS):
        sched.submit(p, n_tokens, greedy=greedy,
                     seed=1000 + i if seeded else None)
    return sched.run()


def _assert_bitexact(ra, rb):
    assert [r.rid for r in rb] == [r.rid for r in ra]
    for a, b in zip(ra, rb):
        assert a.text == b.text
        assert a.stats.private == b.stats.private
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.latency_ms == b.stats.latency_ms
        assert a.stats.fusion_w == b.stats.fusion_w


# ------------------------------------------------------------ parity


@pytest.mark.timeout(540)
def test_macro_k_bitexact_greedy(parts):
    """K=1 and K>1 macro-steps reproduce the per-step reference path bit
    for bit — tokens, latency draws, arrived/fallback accounting and
    fusion weights — under per-row jittery weather, including partial
    final macros (5 tokens, K=3) and mixed private/cloud lanes."""
    ref = _run(parts, macro_k=0, n_tokens=5)
    _assert_bitexact(ref, _run(parts, macro_k=1, n_tokens=5))
    _assert_bitexact(ref, _run(parts, macro_k=3, n_tokens=5))
    _assert_bitexact(ref, _run(parts, macro_k=8, n_tokens=5))
    # the jittery regime must actually exercise per-row fallback
    assert any(0 < r.stats.fallback_tokens < r.stats.tokens for r in ref)


@pytest.mark.timeout(540)
def test_macro_k_bitexact_ring(gemma_parts):
    """gemma3 ring-cache lanes: 20 tokens push every row past window=16,
    so K>1 parity covers per-row ring wrap-around inside the scan."""
    ref = _run(gemma_parts, macro_k=0, n_tokens=20)
    _assert_bitexact(ref, _run(gemma_parts, macro_k=6, n_tokens=20))


def test_macro_k_bitexact_sampling(parts):
    """Seeded non-greedy traffic through the public scheduler API:
    the in-scan select/sample epilogue must replay the per-step path's
    keyed categorical stream exactly (fusion stubbed flat so samples
    actually spread)."""
    ref = _run(parts, macro_k=0, n_tokens=6, greedy=False, seeded=True,
               flat_fusion=True)
    got = _run(parts, macro_k=4, n_tokens=6, greedy=False, seeded=True,
               flat_fusion=True)
    _assert_bitexact(ref, got)
    publics = [r.text for r in got if not r.stats.private]
    assert len(set(publics)) > 1         # distinct per-request keys


def test_macro_k_mixed_greedy_and_sampled(parts):
    """A batch mixing greedy and sampled rows exercises the epilogue's
    per-row select (sample=True trace) in the same scan."""
    def run(mk):
        sched = ContinuousBatchScheduler(
            _engine(parts, mk, flat_fusion=True))
        for i, p in enumerate(PROMPTS):
            sched.submit(p, 5, greedy=(i % 2 == 0), seed=2000 + i)
        return sched.run()
    _assert_bitexact(run(0), run(4))


# -------------------------------------------------- dispatch discipline


def _count(eng):
    """Wrap the deployment's compiled macro-step fns + trace fetch with
    counters: 'macro' counts jitted macro dispatches, 'sync' counts host
    syncs, 'inner' counts Python-level calls into the per-token
    decode-path jits (must be ZERO once the scan is traced — they only
    run inside the macro's XLA program)."""
    counts = {"macro": 0, "sync": 0, "inner": 0}

    def wrap(fn, key):
        def g(*a, **k):
            counts[key] += 1
            return fn(*a, **k)
        return g
    eng.dep.macro_cloud = wrap(eng.dep.macro_cloud, "macro")
    eng.dep.macro_edge = wrap(eng.dep.macro_edge, "macro")
    eng.dep.fetch_traces = wrap(eng.dep.fetch_traces, "sync")
    for name in ("slm_decode", "llm_decode", "fuse_batched",
                 "softmax_batched", "argmax_batched", "sample_batched",
                 "lat_batched"):
        setattr(eng.dep, name, wrap(getattr(eng.dep, name), "inner"))
    return counts


@pytest.mark.timeout(540)
def test_dispatch_discipline_one_sync_per_k(parts):
    """The <=1-host-sync-per-K-tokens contract, counted on the live
    engine: decoding 4 rows x 8 tokens with K=4 takes exactly 2 macro
    dispatches, 2 trace fetches, and ZERO Python-level calls into the
    per-token jits (vs 8 per-token steps each paying several)."""
    k, n_tok = 4, 8
    cloud = [p for p in PROMPTS if not _engine(parts, 0).detector
             .detect(p)][:4]
    eng = _engine(parts, k)
    for i, p in enumerate(cloud):         # warmup: trace the scan
        assert eng.add_request(p, n_tok, True, i)
    while eng.active_count():
        eng.step()
    counts = _count(eng)
    for i, p in enumerate(cloud):
        assert eng.add_request(p, n_tok, True, 100 + i)
    steps = 0
    while eng.active_count():
        eng.step()
        steps += 1
    tokens = len(cloud) * n_tok
    assert steps == n_tok // k == 2
    assert counts["macro"] == steps       # one dispatch per macro
    assert counts["sync"] == steps        # one host sync per K tokens
    assert counts["inner"] == 0, (
        f"per-token jits dispatched from Python inside the macro path: "
        f"{counts}")
    # contract headline: syncs per decoded token is 1/K per lane row set
    assert counts["sync"] * k * len(cloud) == tokens


def test_per_step_path_pays_per_token_syncs(parts):
    """The contrast that motivates the macro-step: the legacy per-step
    path (macro_k=0) makes multiple Python-level jit calls per TOKEN."""
    eng = _engine(parts, 0)
    cloud = [p for p in PROMPTS if not eng.detector.detect(p)][:4]
    for i, p in enumerate(cloud):
        assert eng.add_request(p, 4, True, i)
    while eng.active_count():             # warmup
        eng.step()
    counts = _count(eng)
    for i, p in enumerate(cloud):
        assert eng.add_request(p, 4, True, 100 + i)
    while eng.active_count():
        eng.step()
    assert counts["macro"] == 0
    assert counts["inner"] >= 4 * 3       # >=3 decode-path jits per token


# ------------------------------------------------------------ donation


def test_macro_donates_lane_caches(parts):
    """The macro-step donates the lane cache/logit buffers: references
    held across a step are invalidated (the documented contract), and
    the lane's own state stays live and correct."""
    eng = _engine(parts, 4)
    assert eng.add_request("translate to french: water ->", 8, True, 0)
    stale_sl = eng.cloud_lane.sl
    stale_k = jax.tree.leaves(eng.cloud_lane.s_cache)[0]
    eng.step()
    if jax.default_backend() == "cpu":    # donation supported on CPU
        with pytest.raises(RuntimeError):
            _ = np.asarray(stale_sl)
        with pytest.raises(RuntimeError):
            _ = np.asarray(stale_k)
    # the lane's live buffers are the donated outputs and keep working
    assert np.asarray(eng.cloud_lane.sl).shape[0] == eng.cloud_lane.batch
    while eng.active_count():
        eng.step()
