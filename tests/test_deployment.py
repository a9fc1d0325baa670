"""ServingDeployment placement tests (ISSUE 5 tentpole).

On an 8-fake-device (pod, data, model) mesh with a >1 "model" axis, an
engine constructed through a ``ServingDeployment`` must
  (a) hold SLM+LLM param leaves with non-replicated NamedShardings
      derived from launch/sharding.py RULES_INFERENCE (placed at
      construction, never gathered back);
  (b) reproduce the replicated single-device engine's decode bit for
      bit — greedy AND seeded-sampling traffic, plain 2b AND gemma3
      ring layouts — through the public scheduler API;
  (c) measure strictly lower per-device param bytes than replicated.

Also the ISSUE 5 admission-pipelining satellite (mesh-free): the
continuous scheduler must dispatch the next burst's packed prefill
BETWEEN a macro-step dispatch and its trace-fetch host sync, without
changing any request's output — regression-tested by recording the
dispatch/prefill/sync event order on the live deployment.

In-process mesh tests need a multi-device backend (the mesh-8 CI
entry) and skip on a single-device one; there the subprocess fallback
re-runs this file's ``__main__`` checks under 8 fake CPU devices so
tier-1 always exercises param-sharded serving somewhere.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

MULTI = len(jax.devices()) >= 4
multi = pytest.mark.skipif(
    not MULTI, reason="needs a >=4-device backend "
    "(--xla_force_host_platform_device_count; see the mesh-8 CI entry)")

PROMPTS = [
    "math: compute 12 plus 7 =",
    "my ssn is 123-45-6789, fill the benefits form",       # private
    "translate to french: water ->",
    "my doctor said my blood pressure is 140 over 90",     # private
    "sort ascending: 40 12 77 31 ->",
    "explain how rainbows form",
]
JITTERY = dict(rtt_ms=160, jitter_ms=40.0, cloud_compute_ms=20, seed=7)


def _build(pair):
    from repro.configs.floe_pair import needs_ring_cache, pair_configs
    from repro.core import fusion as FUS
    from repro.models.model import LM
    scfg, lcfg = pair_configs(pair)
    slm = LM(scfg, remat=False, ring_cache=needs_ring_cache(scfg))
    llm = LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return slm, sp, llm, lp, mlp


def _deployment(parts, mesh, rules="inference"):
    from repro.serving.deployment import ServingDeployment
    from repro.serving.latency import LatencyModel
    slm, sp, llm, lp, mlp = parts
    return ServingDeployment(slm, sp, llm, lp, mlp,
                             latency=LatencyModel(**JITTERY),
                             timeout_ms=200.0, max_seq=48, mesh=mesh,
                             rules=rules)


def _run_sched(sched, n_tokens, greedy=True, seeded=False):
    for i, p in enumerate(PROMPTS):
        sched.submit(p, n_tokens, greedy=greedy,
                     seed=3000 + i if seeded else None)
    return sched.run()


def _ref_responses(parts, n_tokens, greedy=True, seeded=False):
    """Replicated single-device reference: the legacy per-token path."""
    from repro.serving.engine import BatchedHybridEngine
    from repro.serving.latency import LatencyModel
    from repro.serving.scheduler import ContinuousBatchScheduler
    slm, sp, llm, lp, mlp = parts
    eng = BatchedHybridEngine(slm, sp, llm, lp, mlp,
                              latency=LatencyModel(**JITTERY),
                              timeout_ms=200.0, max_seq=48, batch_size=4,
                              edge_batch_size=2, macro_k=0)
    return _run_sched(ContinuousBatchScheduler(eng), n_tokens,
                      greedy=greedy, seeded=seeded)


def _assert_bitexact(ra, rb):
    assert [r.rid for r in rb] == [r.rid for r in ra]
    for a, b in zip(ra, rb):
        assert a.text == b.text
        assert a.stats.private == b.stats.private
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.latency_ms == b.stats.latency_ms


def _assert_param_placement(dep):
    """Acceptance: SLM+LLM param leaves carry exactly the declared
    RULES_INFERENCE NamedShardings; whenever the mesh has a >1 "model"
    axis some leaves must be genuinely non-replicated and the measured
    per-device bytes strictly below the replicated footprint."""
    from repro.launch import sharding as SH
    from repro.serving.deployment import _tree_bytes
    sizes = dict(dep.mesh.shape)
    for lm, params, want in ((dep.slm, dep.slm_params,
                              dep.slm_param_shardings),
                             (dep.llm, dep.llm_params,
                              dep.llm_param_shardings)):
        # declared shardings derive from RULES_INFERENCE + the model's
        # declarative axes tree
        rederived = SH.param_shardings(lm.param_axes(), lm.param_specs(),
                                       dep.mesh, SH.RULES_INFERENCE)
        nonrep = 0
        for leaf, sh, rd in zip(jax.tree.leaves(params),
                                jax.tree.leaves(want),
                                jax.tree.leaves(rederived)):
            assert sh.is_equivalent_to(rd, leaf.ndim)
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), \
                (leaf.shape, leaf.sharding, sh)
            nonrep += not leaf.sharding.is_fully_replicated
        if sizes["model"] > 1:
            assert nonrep > 0, "no param leaf spans the model axis"
        # the memory claim, measured on the live shards: per-device
        # bytes strictly shrink vs holding the full tree
        if sizes["model"] > 1:
            assert _tree_bytes(params, per_device=True) \
                < _tree_bytes(params, per_device=False)
    pd = dep.per_device_param_bytes()
    assert pd["total_bytes"] <= pd["replicated_bytes"]
    if sizes["model"] > 1:
        assert pd["total_bytes"] < pd["replicated_bytes"]
        assert pd["slm_bytes"] + pd["llm_bytes"] <= pd["total_bytes"]


def _make_mesh():
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(min(len(jax.devices()), 8))


@pytest.fixture(scope="module")
def mesh():
    return _make_mesh()


@pytest.fixture(scope="module")
def parts_2b():
    return _build("2b")


@multi
def test_serving_mesh_model_parallel_override():
    """make_serving_mesh(model_parallel=): widening the model axis
    trades batch parallelism for a smaller per-device param footprint;
    non-divisor widths are rejected up front."""
    from repro.launch.mesh import make_serving_mesh
    n = min(len(jax.devices()), 8)
    if n % 4 == 0:
        sizes = dict(make_serving_mesh(n, model_parallel=4).shape)
        assert sizes["model"] == 4
        assert sizes["pod"] * sizes["data"] * sizes["model"] == n
    bad = next(w for w in (5, 3, 7) if n % w)
    with pytest.raises(ValueError):
        make_serving_mesh(n, model_parallel=bad)


# --------------------------------------------------------- param sharding


@multi
@pytest.mark.timeout(540)
def test_param_sharded_parity_2b(mesh, parts_2b):
    """Greedy + seeded-sampling parity of the param-sharded deployment
    (macro path AND the per-token macro_k=0 path, engines sharing ONE
    deployment and its compiled entry points) vs the replicated
    single-device engine, plus the placement/memory acceptance
    asserts."""
    from repro.serving.scheduler import ContinuousBatchScheduler
    dep = _deployment(parts_2b, mesh)
    kw = dict(batch_size=4, edge_batch_size=2)

    ref = _ref_responses(parts_2b, 5)
    got = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=4, **kw), 5)
    _assert_bitexact(ref, got)

    # the sharded per-token step path (--macro-k 0) through the SAME
    # deployment: shared compiled prefills/inserts, legacy step jits
    got0 = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=0, **kw), 5)
    _assert_bitexact(ref, got0)

    refs = _ref_responses(parts_2b, 4, greedy=False, seeded=True)
    gots = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=4, **kw), 4, greedy=False, seeded=True)
    _assert_bitexact(refs, gots)

    _assert_param_placement(dep)


@multi
@pytest.mark.timeout(540)
def test_param_sharded_parity_gemma3_ring(mesh):
    """Grouped mixed-attention SLM with window-sized ring caches served
    from sharded params: the grouped (n_groups, g-1, ...) param stacks
    and the ring decode path must survive the RULES_INFERENCE layout
    bit for bit."""
    from repro.serving.scheduler import ContinuousBatchScheduler
    parts = _build("gemma3")
    dep = _deployment(parts, mesh)
    ref = _ref_responses(parts, 8)
    got = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=4, batch_size=4, edge_batch_size=2), 8)
    _assert_bitexact(ref, got)
    _assert_param_placement(dep)


@multi
def test_sequential_engine_through_sharded_deployment(mesh, parts_2b):
    """HybridEngine (sequential reference) also runs off a mesh
    deployment — same sharded params, same compiled entry points — and
    matches its replicated twin."""
    from repro.serving.engine import HybridEngine
    from repro.serving.latency import LatencyModel
    slm, sp, llm, lp, mlp = parts_2b
    plain = HybridEngine(slm, sp, llm, lp, mlp,
                         latency=LatencyModel(**JITTERY),
                         timeout_ms=200.0, max_seq=48)
    sharded = HybridEngine(deployment=_deployment(parts_2b, mesh))
    for rid, p in enumerate(PROMPTS[:3]):
        a = plain.generate(p, 5, rid=rid)
        b = sharded.generate(p, 5, rid=rid)
        assert a[0] == b[0]
        assert a[1].latency_ms == b[1].latency_ms


# ---------------------------------------------------- admission pipelining


def _pipeline_events(macro_k=4):
    """Run staggered traffic (a slot frees while neighbours keep
    decoding) through the continuous scheduler, recording the order of
    macro dispatches, packed-prefill dispatches, and trace-fetch host
    syncs on the live deployment."""
    from repro.serving.engine import BatchedHybridEngine
    from repro.serving.latency import LatencyModel
    from repro.serving.scheduler import ContinuousBatchScheduler
    slm, sp, llm, lp, mlp = _build("2b")
    eng = BatchedHybridEngine(slm, sp, llm, lp, mlp,
                              latency=LatencyModel(rtt_ms=20.0,
                                                   jitter_ms=0.0),
                              timeout_ms=200.0, max_seq=48, batch_size=2,
                              edge_batch_size=1, macro_k=macro_k)
    events = []

    def wrap(fn, tag):
        def g(*a, **k):
            events.append(tag)
            return fn(*a, **k)
        return g
    eng.dep.macro_cloud = wrap(eng.dep.macro_cloud, "dispatch")
    eng.dep.macro_edge = wrap(eng.dep.macro_edge, "dispatch")
    eng.dep.slm_prefill_packed = wrap(eng.dep.slm_prefill_packed,
                                      "prefill")
    eng.dep.fetch_traces = wrap(eng.dep.fetch_traces, "sync")
    sched = ContinuousBatchScheduler(eng)
    public = [p for p in PROMPTS if not eng.detector.detect(p)]
    # rid 0 finishes after one K=4 macro; rids 1-2 keep the lane busy so
    # rid 3's admission prefill must overlap their in-flight macro
    for p, mn in zip(public, (4, 12, 12, 8)):
        sched.submit(p, mn)
    return events, sched.run()


def test_admission_prefill_overlaps_macro_dispatch():
    """ISSUE 5 satellite: the scheduler admits the next burst BETWEEN a
    macro dispatch and its host sync — the packed prefill is dispatched
    while the decode macro is still in flight."""
    events, res = _pipeline_events()
    assert len(res) == 4
    # count dispatches between consecutive syncs: the macro discipline
    # (one dispatch per lane per sync window) must survive pipelining
    window = []
    overlapped = False
    for e in events:
        if e == "sync":
            assert 0 < window.count("dispatch") <= 2, events
            overlapped |= "prefill" in window
            window = []
        else:
            window.append(e)
    # at least one admission burst prefilled between dispatch and sync
    assert overlapped, f"no prefill inside a dispatch->sync window: " \
                       f"{events}"


def test_pipelined_admission_outputs_unchanged():
    """Pipelining shifts wall-clock admission only: tokens, latency
    draws and stats match the per-token (macro_k=0, admit-then-step)
    reference bit for bit."""
    _, res_macro = _pipeline_events(macro_k=4)
    _, res_ref = _pipeline_events(macro_k=0)
    _assert_bitexact(res_ref, res_macro)


def test_entry_points_compile_under_their_own_names(parts_2b):
    """Every jitted entry point carries its attribute's name, so its
    XLA module is ``jit_<entry>`` and a profile names the program."""
    import re
    import jax.numpy as jnp
    dep = _deployment(parts_2b, None)
    entries = {n: getattr(dep, n) for n in dir(dep)
               if not n.startswith("_")
               and hasattr(getattr(dep, n), "lower")}
    assert {"macro_cloud", "macro_edge", "spec_cloud", "insert_slm",
            "insert_row", "grow_block_pages", "slm_prefill_chunk",
            "sample_batched"} <= set(entries)
    assert all(f.__name__ == n for n, f in entries.items())
    probs = jnp.ones((2, 8)) / 8
    for name, args in (("argmax_batched", (probs,)),
                       ("sample_batched", (probs, jnp.zeros(2, jnp.int32),
                                           jnp.zeros(2, jnp.int32)))):
        text = getattr(dep, name).lower(*args).as_text()
        assert re.search(r"module @(\S+)", text).group(1) == f"jit_{name}"


# ----------------------------------------------------- subprocess fallback


@pytest.mark.skipif(
    MULTI, reason="in-process mesh tests already run on this backend")
def test_deployment_subprocess():
    """Single-device tier-1 fallback: re-run the param-sharded parity /
    placement checks in a fresh interpreter with 8 fake CPU devices
    (the device count is locked at first jax init)."""
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
    assert "DEPLOYMENT-OK" in out.stdout


if __name__ == "__main__":
    assert len(jax.devices()) >= 4, "set XLA_FLAGS before running"
    from repro.serving.scheduler import ContinuousBatchScheduler
    m = _make_mesh()
    print(f"mesh: {dict(m.shape)} over {len(jax.devices())} devices")
    parts = _build("2b")
    dep = _deployment(parts, m)
    ref = _ref_responses(parts, 5)
    got = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=4, batch_size=4, edge_batch_size=2), 5)
    _assert_bitexact(ref, got)
    refs = _ref_responses(parts, 4, greedy=False, seeded=True)
    gots = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep, macro_k=4, batch_size=4, edge_batch_size=2), 4,
        greedy=False, seeded=True)
    _assert_bitexact(refs, gots)
    _assert_param_placement(dep)
    pd = dep.per_device_param_bytes()
    print(f"2b: parity ok, per-device {pd['total_bytes']} "
          f"vs replicated {pd['replicated_bytes']} bytes")
    parts_g = _build("gemma3")
    dep_g = _deployment(parts_g, m)
    ref = _ref_responses(parts_g, 8)
    got = _run_sched(ContinuousBatchScheduler.from_deployment(
        dep_g, macro_k=4, batch_size=4, edge_batch_size=2), 8)
    _assert_bitexact(ref, got)
    _assert_param_placement(dep_g)
    print("gemma3: parity + placement ok")
    print("DEPLOYMENT-OK")
