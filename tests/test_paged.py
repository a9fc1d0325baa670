"""Paged lane KV caches (ISSUE 6 tentpole): paged-vs-dense bit-identity
(greedy + seeded, plain and gemma3-ring layouts, per-token and macro),
page-gated admission (soft refusal vs hard reject), page release and
re-admission, COW shared-prefix admission, resident-byte accounting,
and the mesh-sharded paged path (subprocess fallback, like
test_sharded_lanes)."""
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fusion as FUS
from repro.data import tokenizer as TOK
from repro.models.attention import FREED_POS
from repro.models.model import LM
from repro.serving import paging as PAG
from repro.serving.deployment import ServingDeployment
from repro.serving.engine import BatchedHybridEngine
from repro.serving.latency import LatencyModel
from repro.serving.scheduler import (ContinuousBatchScheduler,
                                     ResponseStatus)

LAT = dict(rtt_ms=10, jitter_ms=0)
PREFIX = "you are a helpful assistant. "      # >= 1 page of tokens @ 16
PROMPTS = [
    "math: compute 12 plus 7 =",
    "my ssn is 123-45-6789, fill the benefits form",       # private
    "translate to french: water ->",
    "sort ascending: 40 12 77 31 ->",
    "explain how rainbows form",
    "list three colors",
]


@pytest.fixture(scope="module")
def engine_parts():
    scfg = get_config("floe-slm-2b").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm, llm = LM(scfg, remat=False), LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


@pytest.fixture(scope="module")
def gemma_engine_parts():
    scfg = get_config("floe-slm-gemma3").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm = LM(scfg, remat=False, ring_cache=True)
    llm = LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


def _engine(parts, paged, macro_k=4, **kw):
    slm, sp, llm, lp, mlp = parts
    kw.setdefault("batch_size", 4)
    kw.setdefault("edge_batch_size", 1)
    return BatchedHybridEngine(slm, sp, llm, lp, mlp, max_seq=48,
                               latency=LatencyModel(**LAT),
                               timeout_ms=200.0, macro_k=macro_k,
                               paged=paged, **kw)


def _run_sched(eng, reqs, n_tokens=5):
    sched = ContinuousBatchScheduler(eng)
    for i, (p, prefix) in enumerate(reqs):
        sched.submit(p, n_tokens, greedy=(i % 2 == 0), seed=i,
                     prefix=prefix)
    return sched.run()


def _assert_same(r_dense, r_paged, fusion_ulp=0.0):
    """Bit-identity of the decode streams.  ``fusion_ulp``: on a mesh
    the fusion-WEIGHT telemetry is float32 reduced under a different
    partitioning (pool pages vs cache rows), so XLA legitimately
    reassociates it by an ULP or two; the token/latency streams must
    stay exact regardless (same contract as test_sharded_lanes, which
    omits fusion_w from mesh parity entirely)."""
    assert [r.rid for r in r_paged] == [r.rid for r in r_dense]
    for a, b in zip(r_dense, r_paged):
        assert a.text == b.text, (a.rid, a.text, b.text)
        assert a.stats.private == b.stats.private
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.latency_ms == b.stats.latency_ms
        if fusion_ulp:
            assert len(a.stats.fusion_w) == len(b.stats.fusion_w)
            assert all(abs(x - y) <= fusion_ulp * 1.2e-7
                       for x, y in zip(a.stats.fusion_w,
                                       b.stats.fusion_w)), a.rid
        else:
            assert a.stats.fusion_w == b.stats.fusion_w


# ----------------------------------------------------------- bit-identity


@pytest.mark.parametrize("macro_k", [0, 4])
def test_paged_matches_dense(engine_parts, macro_k):
    """Paged decode must be bit-for-bit the dense engine (the paged=False
    parity oracle), greedy AND seeded sampling, per-token and macro
    cadence, private and cloud lanes."""
    reqs = [(p, None) for p in PROMPTS]
    r_dense = _run_sched(_engine(engine_parts, False, macro_k), reqs)
    r_paged = _run_sched(_engine(engine_parts, True, macro_k), reqs)
    _assert_same(r_dense, r_paged)


def test_paged_matches_dense_prefix(engine_parts):
    """COW shared-prefix admission: same outputs as the dense engine fed
    the concatenated prompts, the preamble prefilled exactly ONCE per
    model, and its pages refcount-shared across the sharing rows."""
    reqs = [(p, PREFIX if i % 2 == 0 else None)
            for i, p in enumerate(PROMPTS[2:])] + [(PROMPTS[0], PREFIX)]
    dense = _engine(engine_parts, False)
    paged = _engine(engine_parts, True)
    calls = {"slm": 0, "llm": 0}
    orig_s, orig_l = paged.dep.slm_build_prefix, paged.dep.llm_build_prefix
    paged.dep.slm_build_prefix = \
        lambda *a, **k: calls.__setitem__("slm", calls["slm"] + 1) \
        or orig_s(*a, **k)
    paged.dep.llm_build_prefix = \
        lambda *a, **k: calls.__setitem__("llm", calls["llm"] + 1) \
        or orig_l(*a, **k)
    r_dense = _run_sched(dense, reqs)
    r_paged = _run_sched(paged, reqs)
    paged.dep.slm_build_prefix, paged.dep.llm_build_prefix = orig_s, orig_l
    _assert_same(r_dense, r_paged)
    assert calls == {"slm": 1, "llm": 1}, calls
    lane = paged.cloud_lane
    entry = lane._prefixes[PREFIX]
    assert entry is not None and entry["share_np"] >= 1
    # drained rows dropped their forks; the registry still holds one
    # reference per shared page, so the preamble pages stay warm
    for pid in entry["pids_s"]:
        assert lane.pager_s.alloc.refcount(pid) == 1


def test_paged_matches_dense_ring(gemma_engine_parts):
    """gemma3-style grouped layout: full-length global leaves AND
    window-sized ring leaves (local page ring) under one block/local
    table pair; 8 tokens pushes rows past window=16 with the reduced
    prompt lengths, covering ring wrap on pages."""
    reqs = [(p, None) for p in PROMPTS]
    r_dense = _run_sched(_engine(gemma_engine_parts, False), reqs,
                         n_tokens=8)
    r_paged = _run_sched(_engine(gemma_engine_parts, True), reqs,
                         n_tokens=8)
    _assert_same(r_dense, r_paged)


# Model-level parity: LM.decode_step on a paged cache against dense
# decode, over hand-built block tables (page_size 4, max_seq 32, ring
# window 16).  Row 0 crosses a page boundary (positions 2-5); row 1
# crosses one and wraps its ring (14-17); both have NO_PAGE tails.  Row
# 2 is parked at FREED_POS with its pages still mapped.
NO = PAG.NO_PAGE
_TABLES = {"block": [[5, 0, NO, NO, NO, NO, NO, NO],
                     [1, 6, 7, 3, 8, NO, NO, NO],
                     [4, 11, NO, NO, NO, NO, NO, NO]],
           "local": [[5, 0, NO, NO], [2, 9, 4, 10], [6, 7, NO, NO]]}
_POS0 = [2, 14, FREED_POS]


def _kv_leaves(cache, axes):
    """Each KV leaf of a cache tree, in sorted key order, with its
    (batch or page, seq or slot) axes moved to the front."""
    def by_key(t):
        return {k: by_key(t[k]) for k in sorted(t)} \
            if isinstance(t, dict) else t
    out = []
    PAG.walk_kv(by_key(cache), axes, lambda leaf, ab: out.append(
        np.moveaxis(np.asarray(leaf), (ab, ab + 1), (0, 1))) or leaf)
    return out


def _table(leaf, max_seq):
    return _TABLES["block" if leaf.shape[1] == max_seq else "local"]


@pytest.mark.parametrize("arch,ring", [("floe-slm-2b", False),
                                       ("floe-slm-gemma3", True),
                                       ("granite-moe-3b-a800m", False)],
                         ids=["plain", "grouped-ring", "moe"])
def test_decode_step_paged_matches_dense(arch, ring):
    """The paged decode step (pools carried through the layer scan, each
    token written in place at [layer, page, offset]) is dense decode bit
    for bit: the live rows' logits at every step, and afterwards every
    page of the pool holds what the dense cache does, with the parked
    row's pages, the NO_PAGE tails and the unmapped pages untouched."""
    cfg = get_config(arch).reduced()
    if ring:              # two groups of (local, global) and a local tail
        cfg = replace(cfg, num_layers=5)
    lm = LM(cfg, remat=False, ring_cache=ring)
    params = lm.init(jax.random.key(0))
    max_seq, ps, n_pages, steps, b = 32, 4, 12, 4, len(_POS0)
    rng = np.random.default_rng(0)
    axes = lm.cache_batch_axes_tree(max_seq)
    dense = PAG.walk_kv(
        lm.init_cache(b, max_seq), axes,
        lambda leaf, ab: jnp.asarray(rng.standard_normal(leaf.shape),
                                     leaf.dtype))
    dense["pos"] = jnp.asarray(_POS0, jnp.int32)

    def pool_of(leaf, ab):
        # each dense row at its table's pages, noise on every other page
        x = np.moveaxis(np.asarray(leaf), (ab, ab + 1), (0, 1))
        pool = rng.standard_normal((n_pages, ps) + x.shape[2:]) \
            .astype(x.dtype)
        for row, pids in enumerate(_table(x, max_seq)):
            for i, pid in enumerate(pids):
                if pid != NO:
                    pool[pid] = x[row, i * ps:(i + 1) * ps]
        return jnp.asarray(np.moveaxis(pool, (0, 1), (ab, ab + 1)))

    paged = PAG.walk_kv(dense, axes, pool_of)
    paged["block"] = jnp.asarray(_TABLES["block"], jnp.int32)
    if ring:
        paged["local"] = jnp.asarray(_TABLES["local"], jnp.int32)
    pools0 = _kv_leaves(paged, axes)
    step = jax.jit(lm.decode_step)
    for _ in range(steps):
        tok = jnp.asarray(rng.integers(3, cfg.vocab_size, (b, 1)),
                          jnp.int32)
        ld, dense = step(params, dense, tok)
        lp, paged = step(params, paged, tok)
        np.testing.assert_array_equal(np.asarray(ld[:2]),
                                      np.asarray(lp[:2]))
    np.testing.assert_array_equal(np.asarray(paged["pos"]),
                                  np.asarray(dense["pos"]))
    for p0, p1, x in zip(pools0, _kv_leaves(paged, axes),
                         _kv_leaves(dense, axes), strict=True):
        want, tab = p0.copy(), _table(x, max_seq)
        for row, start in enumerate(_POS0[:2]):
            for pos in range(start, start + steps):
                slot = pos % x.shape[1]
                want[tab[row][slot // ps], slot % ps] = x[row, slot]
        np.testing.assert_array_equal(p1, want)


# ------------------------------------------------- lazy growth (ISSUE 7)


@pytest.mark.parametrize("macro_k", [0, 4])
def test_lazy_matches_worst_case(engine_parts, macro_k):
    """ISSUE 7 tentpole: lazy reservation (prompt pages + 1, grown at
    page boundaries) must be token-bit-identical to the PR 6 eager
    worst-case reservation, greedy + seeded, per-token and macro.
    With 5-token budgets no row crosses a page boundary, so this pins
    the reservation-size difference itself (NO_PAGE table tails vs
    eagerly mapped ones); boundary crossing is pinned below."""
    reqs = [(p, None) for p in PROMPTS]
    r_worst = _run_sched(_engine(engine_parts, True, macro_k,
                                 lazy_pages=False), reqs)
    r_lazy = _run_sched(_engine(engine_parts, True, macro_k), reqs)
    _assert_same(r_worst, r_lazy)


@pytest.mark.parametrize("macro_k", [0, 4])
def test_lazy_growth_crosses_boundary(engine_parts, macro_k):
    """Rows engineered to decode ACROSS a page boundary (prompt just
    past one page, budget well past the next): growth fires mid-decode
    and the streams stay bit-identical to the eager reservation."""
    prompt = "sum 1 and 2"
    n = len(TOK.encode(prompt + " "))
    ps = 16       # _engine page size; lazy reserves pages_for(n)+1 = 2
    assert PAG.pages_for(n, ps) + 1 < PAG.pages_for(min(n + 20, 48), ps)
    reqs = [(prompt, None), (prompt + " no", None)]
    r_worst = _run_sched(_engine(engine_parts, True, macro_k,
                                 lazy_pages=False), reqs, n_tokens=20)
    eng = _engine(engine_parts, True, macro_k)      # lazy is the default
    r_lazy = _run_sched(eng, reqs, n_tokens=20)
    _assert_same(r_worst, r_lazy)
    # the default pool is worst-case-sized, so growth always succeeds
    # in place — backpressure never fires, but pages genuinely grew
    st = eng.growth_stats()
    assert st["grown_pages"] > 0
    assert st["parks"] == st["evictions"] == st["forced"] == 0


def test_lazy_matches_worst_case_ring(gemma_engine_parts):
    """Lazy growth under the grouped gemma3 layout (full + ring local
    leaves): the local ring is reserved eagerly (fixed size), only the
    full-sequence tables grow."""
    reqs = [(p, None) for p in PROMPTS]
    r_worst = _run_sched(_engine(gemma_engine_parts, True,
                                 lazy_pages=False), reqs, n_tokens=8)
    r_lazy = _run_sched(_engine(gemma_engine_parts, True), reqs,
                        n_tokens=8)
    _assert_same(r_worst, r_lazy)


# ----------------------------------------------- chunked prefill (ISSUE 7)


def _long_prompt():
    p = ("sort these numbers ascending please: "
         "40 12 77 31 55 63 98 2 ->")
    n = len(TOK.encode(p + " "))
    assert 48 < n <= 96 - 6 - 1, n      # beyond max_seq, fits max_ctx
    return p


def test_chunked_matches_oneshot(engine_parts):
    """Chunked prefill must be bit-identical to one-shot prefill for
    prompts that fit a dense row: chunk_width=16 forces every prompt
    through the page-by-page streaming path."""
    reqs = [(p, None) for p in PROMPTS]
    r_oneshot = _run_sched(_engine(engine_parts, True), reqs)
    r_chunked = _run_sched(_engine(engine_parts, True, chunk_width=16),
                           reqs)
    _assert_same(r_oneshot, r_chunked)


def test_chunked_matches_oneshot_ring(gemma_engine_parts):
    r_oneshot = _run_sched(_engine(gemma_engine_parts, True),
                           [(p, None) for p in PROMPTS], n_tokens=8)
    r_chunked = _run_sched(_engine(gemma_engine_parts, True,
                                   chunk_width=16),
                           [(p, None) for p in PROMPTS], n_tokens=8)
    _assert_same(r_oneshot, r_chunked)


def test_long_prompt_served(engine_parts):
    """A prompt longer than the dense row width (max_seq=48) is served
    untruncated through chunked prefill when the deployment's paged
    context (max_ctx=96) covers it.  No dense oracle exists above
    max_seq, so the cross-checks are per-token vs macro agreement and
    chunk-width invariance (W=48 vs W=16)."""
    slm, sp, llm, lp, mlp = engine_parts
    dep = ServingDeployment(slm, sp, llm, lp, mlp,
                            latency=LatencyModel(**LAT),
                            timeout_ms=200.0, max_seq=48, max_ctx=96)
    prompt = _long_prompt()

    def run(**kw):
        eng = BatchedHybridEngine(deployment=dep, batch_size=2,
                                  edge_batch_size=1, paged=True, **kw)
        return _run_sched(eng, [(prompt, None)], n_tokens=6)

    r_tok = run(macro_k=0)
    assert not r_tok[0].truncated and r_tok[0].stats.tokens == 6
    _assert_same(r_tok, run(macro_k=4))
    _assert_same(r_tok, run(macro_k=0, chunk_width=16))
    # the same prompt on a max_ctx=max_seq deployment is truncated —
    # and now SAYS so instead of lying by omission
    r48 = _run_sched(_engine(engine_parts, True), [(prompt, None)],
                     n_tokens=6)
    assert r48[0].truncated


# ------------------------------------------------------ admission gating


def _demand(prompt, max_new, max_seq=48, ps=16):
    ids = TOK.encode(prompt + " ")[: max_seq - max_new - 1]
    return PAG.pages_for(min(len(ids) + max_new, max_seq), ps)


def test_page_gated_admission_refusals(engine_parts):
    """Satellite: both refusal kinds.  A demand beyond TOTAL pool
    capacity is a HARD reject (surfaced via pop_rejected, never
    retried); a demand beyond the current FREE list is a soft refusal
    (admitted fine after pages free up), bit-identical to a fresh
    admit.  Plus resident-byte accounting across the row lifecycle."""
    eng = _engine(engine_parts, True, batch_size=3, pool_pages=2)
    geo_s = eng.dep.paged_geometry(eng.slm)
    geo_l = eng.dep.paged_geometry(eng.llm)
    a, c = "list three colors", "hi"
    assert _demand(a, 2) == 2 and _demand(c, 2) == 1
    assert eng.resident_kv_bytes() == 0

    # fresh-admit reference for C (seeded: the sampling keys are
    # counter-based on (rid, step), so a later re-admit must replay it)
    assert eng.add_request(c, 2, False, 7, 3)
    ref = {}
    while eng.active_count():
        for rid, text, _ in eng.step():
            ref[rid] = text
    assert eng.cloud_lane.pager_s.alloc.free_pages == 2   # all released

    assert eng.add_request(a, 2, True, 0)                 # 2 pages: fits
    assert eng.resident_kv_bytes() == 2 * (geo_s["page_bytes_full"]
                                           + geo_l["page_bytes_full"])
    # soft refusal: free slot exists, free pages don't; NOT a reject
    assert not eng.add_request(c, 2, False, 7, 3)
    assert eng.pop_rejected() == []
    # hard reject: 3-page demand can NEVER fit the 2-page pool
    assert _demand("what time is it now", 40) == 3
    assert not eng.add_request("what time is it now", 40, True, 9)
    rejected = eng.pop_rejected()
    assert [rid for rid, _ in rejected] == [9]
    assert "exceeds pool capacity" in rejected[0][1]

    while eng.active_count():                             # drain A
        eng.step()
    assert eng.cloud_lane.pager_s.alloc.free_pages == 2
    assert eng.resident_kv_bytes() == 0
    # the soft-refused request admits now and replays its fresh-admit
    # sample stream bit for bit (same rid/seed counters)
    assert eng.add_request(c, 2, False, 7, 3)
    got = {}
    while eng.active_count():
        for rid, text, _ in eng.step():
            got[rid] = text
    assert got == ref
    # a hard reject is not retried by the scheduler either
    sched = ContinuousBatchScheduler(eng)
    sched.submit("what time is it now", 40)
    res = sched.run()
    assert len(res) == 1 and res[0].error is not None
    assert res[0].status is ResponseStatus.REJECTED
    assert res[0].text == "" and res[0].stats.tokens == 0


def test_hard_reject_names_offending_model(engine_parts):
    """ISSUE 7 satellite: the hard-reject reason must name the model
    whose pool actually overflowed — an LLM-pool overflow used to be
    reported as the SLM's demand/capacity."""
    # SLM pool is the bottleneck
    eng = _engine(engine_parts, True, batch_size=3, pool_pages=2,
                  llm_pool_pages=64)
    assert not eng.add_request("what time is it now", 40, True, 9)
    (rid, reason), = eng.pop_rejected()
    assert rid == 9
    assert reason.startswith("slm page demand 3")
    assert "exceeds pool capacity 2 pages" in reason
    # LLM pool is the bottleneck (SLM pool left at the default size)
    eng = _engine(engine_parts, True, batch_size=3, llm_pool_pages=2)
    assert not eng.add_request("what time is it now", 40, True, 11)
    (rid, reason), = eng.pop_rejected()
    assert rid == 11
    assert reason.startswith("llm page demand 3")
    assert "exceeds pool capacity 2 pages" in reason


def test_paged_park_release_readmit(engine_parts):
    """Satellite: a drained row's pages return to the free list at
    collect time, its device tables are sentineled (NO_PAGE / FREED_POS
    parking), the surviving row keeps decoding, and re-admission into
    the recycled pages is bit-identical to a fresh admit (seeded)."""
    eng = _engine(engine_parts, True, batch_size=2)
    p2 = "sort ascending: 40 12 77 31 ->"
    assert eng.add_request(p2, 4, False, 2, 5)            # fresh-admit ref
    ref = {}
    while eng.active_count():
        for rid, text, _ in eng.step():
            ref[rid] = text
    lane = eng.cloud_lane
    total_free = lane.pager_s.alloc.free_pages
    assert lane.pager_s.alloc.live_pages == 0

    assert eng.add_request("translate to french: water ->", 2, True, 0)
    assert eng.add_request("explain how rainbows form", 10, True, 1)
    slot = next(i for i, s in enumerate(lane.slots) if s and s.rid == 0)
    done = []
    while not any(d[0] == 0 for d in done):
        done += eng.step()
    # rid 0 drained: pages back on the free list, device row parked
    assert lane.pager_s.rows[slot] is None
    assert lane.pager_l.rows[slot] is None
    assert int(lane.s_cache["pos"][slot]) == FREED_POS
    assert int(lane.l_cache["pos"][slot]) == FREED_POS
    assert np.all(np.asarray(lane.s_cache["block"][slot]) == PAG.NO_PAGE)
    used = lane.pager_s.alloc.live_pages
    assert lane.pager_s.alloc.free_pages == total_free - used
    for _ in range(3):                                    # rid 1 decodes on
        eng.step()
    while eng.active_count():
        eng.step()
    assert lane.pager_s.alloc.live_pages == 0
    # re-admit the reference request into the recycled pages
    assert eng.add_request(p2, 4, False, 2, 5)
    got = {}
    while eng.active_count():
        for rid, text, _ in eng.step():
            got[rid] = text
    assert got == ref


# ------------------------------------------------------------------ mesh

MULTI = len(jax.devices()) >= 4


@pytest.mark.skipif(
    MULTI, reason="mesh paged parity runs in-process on this backend "
    "via test_sharded_lanes (engines default paged=True)")
def test_paged_mesh_subprocess():
    """8-fake-device mesh: the PAGED engine on a sharded deployment must
    reproduce the DENSE engine on the same deployment bit for bit
    (pool pages over ("pod","data"), KV width over "model")."""
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
    assert "PAGED-MESH-OK" in out.stdout


def _mesh_main():
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.deployment import ServingDeployment
    assert len(jax.devices()) >= 4, "set XLA_FLAGS before running"
    mesh = make_serving_mesh(min(len(jax.devices()), 8))
    print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")
    scfg = get_config("floe-slm-2b").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm, llm = LM(scfg, remat=False), LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    # page_size=4 with 8-token budgets: every short-prompt row's decode
    # crosses past its prompt-pages+1 reservation, so the lazy run below
    # genuinely exercises growth scatters on the mesh
    dep = ServingDeployment(slm, sp, llm, lp, mlp,
                            latency=LatencyModel(**LAT), max_seq=48,
                            page_size=4, mesh=mesh, rules="inference")

    def run(paged, **kw):
        eng = BatchedHybridEngine(deployment=dep, batch_size=4,
                                  edge_batch_size=1, timeout_ms=200.0,
                                  macro_k=4, paged=paged, **kw)
        sched = ContinuousBatchScheduler(eng)
        for i, p in enumerate(PROMPTS):
            sched.submit(p, 8, greedy=(i % 2 == 0), seed=i)
        return sched.run(), eng

    r_dense, _ = run(False)
    r_paged, eng = run(True)
    _assert_same(r_dense, r_paged, fusion_ulp=4)
    # lazy growth (the default above) vs eager worst-case reservation:
    # the growth scatters go through the sharded admission path, and
    # the token streams must stay bit-identical on the mesh too
    r_worst, _ = run(True, lazy_pages=False)
    _assert_same(r_worst, r_paged)
    assert eng.growth_stats()["grown_pages"] > 0
    # pool leaves genuinely span the mesh (pages over the batch axes)
    lane = eng.cloud_lane
    assert any(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(lane.s_cache)), \
        "no paged lane-cache leaf spans the mesh"
    print("PAGED-MESH-OK")


if __name__ == "__main__":
    _mesh_main()
