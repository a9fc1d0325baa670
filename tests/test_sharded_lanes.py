"""Mesh-sharded continuous-decode lane tests (ISSUE 3 tentpole).

The lane caches of ``BatchedHybridEngine(mesh=...)`` must (a) carry the
``launch/sharding.py`` lane layout on every leaf — batch rows over
("pod", "data"), wide KV dims over "model" — and (b) reproduce the
single-device engine's greedy decode bit for bit, request for request,
including continuous-batching refills through the shard_map row scatter.

The in-process tests need a multi-device backend; they run for real
under ``--xla_force_host_platform_device_count=8`` (the mesh-8 CI matrix
entry) and skip on a single-device backend.  On a single-device backend
the subprocess test takes over: it re-runs this file's ``__main__``
checks in a fresh interpreter with 8 fake CPU devices, so tier-1 always
exercises the sharded path somewhere.
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


def _run_pair(pair, mesh, n_tokens=6, mesh_macro_k=4):
    """Same workload through a single-device and a mesh-sharded batched
    engine; 6 requests into a 4-wide cloud lane exercises the refill
    (shard_map scatter into freed rows) on the sharded path too.

    The reference engine runs the LEGACY per-token step path
    (macro_k=0) on a single device while the mesh engine decodes in
    K=4 macro-steps (the ISSUE 4 scan), so this parity spans both the
    sharding and the macro-step rewrite at once — the scan must keep
    the per-leaf lane shardings pinned across iterations.
    ``mesh_macro_k=0`` instead covers the sharded PER-TOKEN step path
    (still reachable via --macro-k 0), which must not lose its
    sharding constraints either."""
    from repro.serving.engine import BatchedHybridEngine
    from repro.serving.latency import LatencyModel
    from repro.serving.scheduler import ContinuousBatchScheduler
    slm, sp, llm, lp, mlp = _build(pair)
    lat = dict(rtt_ms=160, jitter_ms=40.0, cloud_compute_ms=20, seed=7)
    kw = dict(max_seq=48, batch_size=4, edge_batch_size=2,
              timeout_ms=200.0)
    e_plain = BatchedHybridEngine(slm, sp, llm, lp, mlp,
                                  latency=LatencyModel(**lat),
                                  macro_k=0, **kw)
    e_mesh = BatchedHybridEngine(slm, sp, llm, lp, mlp,
                                 latency=LatencyModel(**lat), mesh=mesh,
                                 macro_k=mesh_macro_k, **kw)
    s1 = ContinuousBatchScheduler(e_plain)
    s2 = ContinuousBatchScheduler(e_mesh)
    for p in PROMPTS:
        s1.submit(p, n_tokens)
        s2.submit(p, n_tokens)
    return s1.run(), s2.run(), e_mesh


def _assert_parity(r_plain, r_mesh):
    assert [r.rid for r in r_mesh] == [r.rid for r in r_plain]
    for a, b in zip(r_plain, r_mesh):
        assert a.text == b.text
        assert a.stats.private == b.stats.private
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.latency_ms == b.stats.latency_ms


def _assert_layout(eng):
    """Every live lane-cache leaf must carry exactly the
    launch/sharding.py lane layout; whenever the mesh factoring makes a
    dim shardable (divisible batch, model axis > 1) the lane must
    genuinely span the mesh.  Derived from the mesh rather than
    hardcoded so odd real-device counts (5, 7, ...) don't fail."""
    lane = eng.cloud_lane
    sizes = dict(eng.mesh.shape)
    expect_batch = (sizes["pod"] * sizes["data"] > 1
                    and lane.batch % (sizes["pod"] * sizes["data"]) == 0)
    expect_wide = sizes["model"] > 1        # head_dim=32 always divides
    for lm, cache, pager in ((eng.slm, lane.s_cache, lane.pager_s),
                             (eng.llm, lane.l_cache, lane.pager_l)):
        if getattr(eng, "paged", False):
            # paged lanes: pool pages take the batch mesh axes, KV width
            # keeps "model"; tables/pos are host-managed -> replicated
            lp = (pager.local_alloc.num_pages
                  if pager.local_alloc is not None else 0)
            want = eng.dep.paged_lane_shardings(
                lm, lane.batch, pager.alloc.num_pages, lp)
        else:
            want = eng.dep.lane_shardings(lm, lane.batch)
        spanned = batch_sharded = wide_sharded = False
        for leaf, sh in zip(jax.tree.leaves(cache), jax.tree.leaves(want)):
            if leaf.size == 0:
                # a zero-size leaf (an empty layer group) holds no bytes
                # on any device, so it has no layout to check: jit
                # outputs give it the trivial PartitionSpec()
                continue
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), \
                (leaf.shape, leaf.sharding, sh)
            spec = sh.spec
            # NB device_set covers the whole mesh even for replicated
            # leaves — only a non-replicated sharding truly spans it
            spanned |= not leaf.sharding.is_fully_replicated
            batch_sharded |= any(
                x in (("pod", "data"), "data", "pod") for x in spec if x)
            wide_sharded |= "model" in spec
        if expect_batch:
            assert batch_sharded, "no batch-sharded lane-cache leaf"
        if expect_wide:
            assert wide_sharded, "no model-sharded wide cache dim"
        if expect_batch or expect_wide:
            assert spanned, "lane cache does not span the mesh"


def _make_mesh():
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(min(len(jax.devices()), 8))


@pytest.fixture(scope="module")
def mesh():
    return _make_mesh()


@multi
def test_serving_mesh_shape(mesh):
    """make_serving_mesh factoring contract, derived from the actual
    device count (odd counts legitimately get model=1)."""
    n = min(len(jax.devices()), 8)
    sizes = dict(mesh.shape)
    assert set(sizes) == {"pod", "data", "model"}
    assert sizes["pod"] * sizes["data"] * sizes["model"] == n
    assert sizes["model"] == (2 if n % 2 == 0 and n >= 4 else 1)


@multi
def test_sharded_parity_and_layout_2b(mesh):
    r_plain, r_mesh, eng = _run_pair("2b", mesh)
    _assert_parity(r_plain, r_mesh)
    _assert_layout(eng)


@multi
def test_sharded_per_step_parity_2b(mesh):
    """The sharded PER-TOKEN step path (macro_k=0, the pre-macro
    reference that --macro-k 0 still serves with) keeps its sharding
    constraints and parity too."""
    r_plain, r_mesh, eng = _run_pair("2b", mesh, n_tokens=4,
                                     mesh_macro_k=0)
    _assert_parity(r_plain, r_mesh)
    _assert_layout(eng)


@multi
def test_sharded_parity_gemma3_ring(mesh):
    """Grouped mixed-attention layout with window-sized ring caches:
    per-row ring writes and the grouped (n_groups, g-1, B, ...) batch
    axis must survive sharding.  20 tokens pushes rows past window=16,
    so ring wrap-around happens on sharded caches."""
    r_plain, r_mesh, eng = _run_pair("gemma3", mesh, n_tokens=20)
    _assert_parity(r_plain, r_mesh)
    _assert_layout(eng)


@pytest.mark.skipif(
    MULTI, reason="in-process mesh tests already run on this backend")
def test_sharded_lanes_subprocess():
    """Single-device tier-1 fallback: re-run the parity/layout checks in
    a fresh interpreter with 8 fake CPU devices (the device count is
    locked at first jax init, so it cannot be changed in-process)."""
    env = dict(os.environ)
    # APPEND: for duplicated XLA flags the last occurrence wins, so the
    # forced 8 must follow any device count already in the environment
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    # stay under CI's pytest --timeout=600 so a slow run surfaces this
    # informative TimeoutExpired / assert instead of an opaque
    # thread-timeout kill
    out = subprocess.run([sys.executable, __file__], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"\n--- stdout\n{out.stdout}" \
                                f"\n--- stderr\n{out.stderr}"
    assert "SHARDED-LANES-OK" in out.stdout


if __name__ == "__main__":
    assert len(jax.devices()) >= 4, "set XLA_FLAGS before running"
    m = _make_mesh()
    print(f"mesh: {dict(m.shape)} over {len(jax.devices())} devices")
    for pair_name, ntok, mk in (("2b", 6, 4), ("2b", 4, 0),
                                ("gemma3", 20, 4)):
        r_plain, r_mesh, eng_m = _run_pair(pair_name, m, n_tokens=ntok,
                                           mesh_macro_k=mk)
        _assert_parity(r_plain, r_mesh)
        _assert_layout(eng_m)
        print(f"{pair_name} (mesh macro_k={mk}): parity + layout ok")
    print("SHARDED-LANES-OK")
