"""Fault-injected cloud link tests (ISSUE 9 tentpole).

The deployment-level ``FaultModel`` turns the cloud link from "slow"
into "lossy / down": counter-based per-(rid, step) reply LOSS, seeded
periodic OUTAGE windows, a per-row circuit breaker that degrades
repeatedly failing rows to SLM-only decode, and deadline cancellation.
The contracts under test:

  (a) fault_rate=0 / fault=None is the bit-exact oracle: the plumbing
      must not perturb today's engine at all (the existing parity
      suites lock the fault-free matrix; here we lock the
      normalization and the all-zero telemetry);
  (b) under a NONZERO FaultModel the sequential engine, the per-token
      batched path and the K-token macro scan stay bit-identical to
      each other — the weather is counter-based and the host breaker
      mirror replays the device recurrence exactly;
  (c) injected faults behave: all-lost links never fuse cloud logits,
      breakers trip (and recover when the weather clears), degraded
      tokens charge edge-only latency;
  (d) deadlines cancel identically on every path, releasing pages and
      adapter pins, with ``Response.status`` reporting CANCELLED;
  (e) the scheduler watchdog raises a diagnostic RuntimeError instead
      of spinning when the engine stops making progress.

The mesh variant runs in-process on a >=4-device backend and through
the subprocess fallback (8 fake CPU devices) on single-device tier-1,
like tests/test_sharded_lanes.py.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fusion as FUS
from repro.core import lora as LORA
from repro.models.model import LM
from repro.serving.deployment import ServingDeployment
from repro.serving.engine import BatchedHybridEngine
from repro.serving.latency import FaultModel, LatencyModel
from repro.serving.scheduler import (ContinuousBatchScheduler, Response,
                                     ResponseStatus, Scheduler, summarize)

MULTI = len(jax.devices()) >= 4
multi = pytest.mark.skipif(
    not MULTI, reason="needs a >=4-device backend "
    "(--xla_force_host_platform_device_count; see the mesh-8 CI entry)")

# short enough (char tokenizer) that no prompt truncates at
# max_seq=48 even with the 20-token ring run
PROMPTS = [
    "math: 12 plus 7 =",
    "my ssn is 123-45-6789",     # private (SSN regex)
    "translate: water ->",
    "my doctor said rest",       # private (NER keyword + cue)
    "sort: 40 12 77 31 ->",
    "explain rainbows",
]
# jittery weather so rows genuinely mix arrived/fallback per step even
# before any injected fault
JITTERY = dict(rtt_ms=160, jitter_ms=40.0, cloud_compute_ms=20, seed=7)
JITTERY_EDGE = 65.0          # LatencyModel default edge_compute_ms
# lossy + bursty weather that trips breakers by construction and still
# lets probes succeed: outage_len >= breaker_n, so each full outage
# window alone trips an attempting row.  Whatever the seeded phase, the
# first full window starts by step outage_period - 1, so a request of
# CHAOS_TRIP_TOKENS tokens trips, and one of CHAOS_DEGRADE_TOKENS also
# decodes at least one token degraded
CHAOS = dict(loss_rate=0.25, outage_period=5, outage_len=3, seed=3,
             breaker_n=2, breaker_m=3)
CHAOS_TRIP_TOKENS = CHAOS["outage_period"] + CHAOS["breaker_n"] - 1
CHAOS_DEGRADE_TOKENS = CHAOS_TRIP_TOKENS + 1


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


def _dep(parts, fault=None, mesh=None, **kw):
    slm, sp, llm, lp, mlp = parts
    return ServingDeployment(slm, sp, llm, lp, mlp,
                             latency=LatencyModel(**JITTERY),
                             timeout_ms=200.0, max_seq=48,
                             fault=fault, mesh=mesh, **kw)


def _run_batched(dep, macro_k, n_tokens, greedy=True, seeded=False,
                 deadline_ms=None, prompts=PROMPTS):
    sched = ContinuousBatchScheduler.from_deployment(
        dep, batch_size=4, edge_batch_size=2, macro_k=macro_k)
    for i, p in enumerate(prompts):
        sched.submit(p, n_tokens, greedy=greedy,
                     seed=1000 + i if seeded else None,
                     deadline_ms=deadline_ms)
    return sched.run(), sched.engine


def _run_sequential(dep, n_tokens, deadline_ms=None, prompts=PROMPTS):
    sched = Scheduler.from_deployment(dep)
    for p in prompts:
        sched.submit(p, n_tokens, deadline_ms=deadline_ms)
    return sched.run(), sched.engine


def _assert_bitexact(ra, rb, faults=True, fusion=True):
    """Token, latency, clock and fault-accounting streams must be EXACT
    across paths.  The fusion-weight telemetry is compared to 1e-5
    like test_serving's sequential-vs-batched lock: the in-jit fault
    draws + breaker arithmetic interleave with the alignment-MLP math
    inside the macro scan, so XLA fuses the weight reduction a ULP
    differently than the separately-compiled per-token program (the
    masks and everything downstream stay bit-equal).  ``fusion=False``
    drops it entirely for mesh runs (test_sharded_lanes contract)."""
    assert [r.rid for r in rb] == [r.rid for r in ra]
    for a, b in zip(ra, rb):
        assert a.text == b.text
        assert a.status is b.status
        assert a.stats.private == b.stats.private
        assert a.stats.tokens == b.stats.tokens
        assert a.stats.cloud_tokens == b.stats.cloud_tokens
        assert a.stats.fallback_tokens == b.stats.fallback_tokens
        assert a.stats.latency_ms == b.stats.latency_ms
        if fusion:
            np.testing.assert_allclose(a.stats.fusion_w,
                                       b.stats.fusion_w, atol=1e-5)
        if faults:
            assert a.stats.degraded_tokens == b.stats.degraded_tokens
            assert a.stats.cloud_lost == b.stats.cloud_lost
            assert a.stats.clock_ms == b.stats.clock_ms


# ------------------------------------------------------ fault-free oracle


def test_zero_fault_normalizes_to_oracle(parts):
    """An all-zero FaultModel IS the fault-free oracle: the deployment
    normalizes it to None, no fault entry point is compiled, and a
    served trace reports all-zero fault telemetry."""
    dep = _dep(parts, fault=FaultModel(loss_rate=0.0, outage_period=0,
                                       outage_len=0))
    assert dep.fault is None
    assert dep.fault_batched is None and dep.fault_request is None
    res, eng = _run_batched(dep, macro_k=4, n_tokens=4)
    assert eng.health_stats() == dict(
        losses=0, outage_steps=0, breaker_trips=0, breaker_recoveries=0,
        degraded_tokens=0, cancellations=0)
    summ = summarize(res)
    assert summ["degraded_token_frac"] == 0.0 and summ["cancelled"] == 0
    assert summ["p99_token_latency_ms"] >= summ["p95_token_latency_ms"] > 0
    assert all(r.status is ResponseStatus.OK
               and r.degraded_tokens == 0 and r.cloud_lost == 0
               for r in res)


# -------------------------------------------------- faulty-path parity


@pytest.mark.timeout(540)
def test_fault_parity_across_paths(parts):
    """Under a nonzero FaultModel the sequential engine, the per-token
    batched path and K=1/K=4 macro scans are bit-identical — tokens,
    latency charges, arrived/fallback/degraded/lost accounting and the
    simulated clock — because loss draws are counter-based and the host
    breaker mirror replays the device carry's recurrence exactly."""
    dep = _dep(parts, fault=FaultModel(**CHAOS))
    n_tokens = 8
    assert n_tokens >= CHAOS_DEGRADE_TOKENS
    ref, eng = _run_batched(dep, macro_k=0, n_tokens=n_tokens)
    _assert_bitexact(ref, _run_batched(dep, macro_k=1,
                                       n_tokens=n_tokens)[0])
    _assert_bitexact(ref, _run_batched(dep, macro_k=4,
                                       n_tokens=n_tokens)[0])
    seq, _ = _run_sequential(dep, n_tokens=n_tokens)
    _assert_bitexact(ref, seq)
    # the weather actually bit (by construction, see CHAOS): some cloud
    # attempt was injected-lost and some token decoded under a tripped
    # breaker
    assert sum(r.cloud_lost for r in ref) >= 1
    assert sum(r.degraded_tokens for r in ref) >= 1
    assert eng.health_stats()["breaker_trips"] >= 1


def test_fault_parity_sampled(parts):
    """Seeded non-greedy traffic under faults: the in-scan sample
    epilogue and the fault mask compose — macro and per-token paths
    replay the identical keyed categorical stream over the identically
    masked fused distribution."""
    dep = _dep(parts, fault=FaultModel(**CHAOS))
    ref, _ = _run_batched(dep, macro_k=0, n_tokens=6, greedy=False,
                          seeded=True)
    got, _ = _run_batched(dep, macro_k=3, n_tokens=6, greedy=False,
                          seeded=True)
    _assert_bitexact(ref, got)


@pytest.mark.timeout(540)
def test_fault_parity_ring(gemma_parts):
    """gemma3 ring-cache lanes under faults: 20 tokens push rows past
    window=16, so the breaker carry and the fault mask ride through
    per-row ring wrap-around inside the scan."""
    dep = _dep(gemma_parts, fault=FaultModel(**CHAOS))
    ref, _ = _run_batched(dep, macro_k=0, n_tokens=20)
    _assert_bitexact(ref, _run_batched(dep, macro_k=6, n_tokens=20)[0])


# ------------------------------------------------- injected-fault behavior


def test_all_lost_never_fuses_and_trips(parts):
    """loss_rate=1: every cloud reply drops, so no token ever fuses
    cloud logits, every public token is charged either the fallback
    wait (failed attempt) or edge-only (degraded), the breaker trips
    and never recovers (probes always fail)."""
    fault = FaultModel(loss_rate=1.0, breaker_n=2, breaker_m=3, seed=1)
    dep = _dep(parts, fault=fault)
    res, eng = _run_batched(dep, macro_k=4, n_tokens=8)
    edge32 = float(np.float32(JITTERY_EDGE))
    fb32 = max(edge32, float(np.float32(200.0)))
    for r in res:
        if r.stats.private:
            continue
        assert r.stats.cloud_tokens == 0
        assert r.stats.fallback_tokens == r.stats.tokens
        assert r.degraded_tokens >= 1          # n=2 trips within 8 tokens
        assert r.cloud_lost == r.stats.tokens - r.degraded_tokens
        assert all(x in (edge32, fb32) for x in r.stats.latency_ms)
        # degraded tokens charge edge-only — strictly cheaper than the
        # fallback wait the failed attempts pay
        assert r.stats.latency_ms.count(edge32) == r.degraded_tokens
    h = eng.health_stats()
    assert h["breaker_trips"] >= 1 and h["breaker_recoveries"] == 0
    assert h["losses"] >= 1 and h["degraded_tokens"] >= 1


def test_outage_trips_then_recovers(parts):
    """A pure outage burst (no loss): rows fail for outage_len
    consecutive steps, trip, sit out the backoff, then the re-entry
    probe lands in clear weather and RECOVERS — cloud service resumes
    within the same request."""
    # period 6 guarantees a FULL 3-step window within any 14-step run
    # regardless of the seeded phase offset; n == outage_len so the
    # window's last failure trips, m=2 ends inside the 3 clear steps,
    # and the probe lands in clear weather
    fault = FaultModel(loss_rate=0.0, outage_period=6, outage_len=3,
                       breaker_n=3, breaker_m=2, seed=0)
    dep = _dep(parts, fault=fault)
    res, eng = _run_batched(dep, macro_k=4, n_tokens=14)
    h = eng.health_stats()
    assert h["breaker_trips"] >= 1
    assert h["breaker_recoveries"] >= 1
    assert h["losses"] == 0 and h["outage_steps"] >= 3
    # cloud fusion resumed after recovery on at least one public row
    assert any(not r.stats.private and r.stats.cloud_tokens > 0
               for r in res)


# --------------------------------------------------- deadline cancellation


def test_deadline_cancels_identically_on_every_path(parts):
    """``deadline_ms`` bounds the SIMULATED clock with the same rule on
    every path — token t emits iff the clock after t-1 is under the
    deadline — so the cancelled prefix is bit-identical between the
    sequential engine, the per-token path and the macro scan, and the
    partial text surfaces with status CANCELLED."""
    dep = _dep(parts, fault=FaultModel(**CHAOS))
    # under the edge floor (65 ms/token) even a private row needs
    # > 400 ms of simulated clock for its 7th token: every row —
    # private edge-only, public, degraded — cancels mid-request, and
    # none at token 0 (the clock starts at 0 < deadline)
    deadline = 400.0
    ref, eng = _run_batched(dep, macro_k=0, n_tokens=10,
                            deadline_ms=deadline)
    _assert_bitexact(ref, _run_batched(dep, macro_k=4, n_tokens=10,
                                       deadline_ms=deadline)[0])
    _assert_bitexact(ref, _run_sequential(dep, n_tokens=10,
                                          deadline_ms=deadline)[0])
    assert all(r.status is ResponseStatus.CANCELLED and r.cancelled
               for r in ref)
    assert all(0 < r.stats.tokens < 10 and r.text for r in ref)
    # the emitted prefix is exactly the tokens whose start-clock was
    # under the deadline
    for r in ref:
        clock = np.cumsum([0.0] + r.stats.latency_ms[:-1])
        assert (clock < deadline).all()
        assert r.stats.clock_ms >= deadline
    assert eng.health_stats()["cancellations"] == len(PROMPTS)
    # cancelled rows were parked/released: nothing active, no live pages
    assert eng.active_count() == 0
    for lane in (eng.cloud_lane, eng.edge_lane):
        for pager in (lane.pager_s, lane.pager_l):
            if pager is not None:
                assert pager.alloc.live_pages == 0


def test_deadline_releases_adapter_pins(parts):
    """A cancelled adapterful request drops its slot pin — the resident
    bank is reusable immediately (no leaked refcount)."""
    slm = parts[0]
    dep = _dep(parts, adapter_slots=1)
    sched = ContinuousBatchScheduler.from_deployment(
        dep, batch_size=2, edge_batch_size=1, macro_k=2)
    sched.engine.adapters.register(
        "u0", LORA.init_adapter(slm, jax.random.key(5), rank=2,
                                r_max=dep.adapter_rank))
    sched.submit(PROMPTS[0], 8, adapter_id="u0",
                 deadline_ms=JITTERY_EDGE * 2 + 1.0)
    (r,) = sched.run()
    assert r.status is ResponseStatus.CANCELLED and 0 < r.stats.tokens < 8
    st = sched.engine.adapter_stats()
    assert st["pinned"] == 0, st
    # the slot is genuinely free: a fresh adapterful request admits
    sched.submit(PROMPTS[0], 2, adapter_id="u0")
    (r2,) = sched.run()
    assert r2.status is ResponseStatus.OK and r2.stats.tokens == 2


# ----------------------------------------------------- watchdog / status


def test_watchdog_raises_diagnostics(parts):
    """A run() that stops making progress — nothing admits, rejects or
    completes — must raise the wedge post-mortem, not spin forever."""
    dep = _dep(parts)
    sched = ContinuousBatchScheduler.from_deployment(
        dep, batch_size=2, edge_batch_size=1, macro_k=2)
    sched.watchdog_iters = 4
    # a lane that never frees a slot: every admission attempt refuses
    sched.engine.add_requests = lambda reqs: [False] * len(reqs)
    sched.submit(PROMPTS[0], 4)
    with pytest.raises(RuntimeError) as e:
        sched.run()
    msg = str(e.value)
    assert "wedged" in msg and "pending rids: [0]" in msg
    assert "slots free" in msg and "health" in msg


def test_response_status_severity():
    """One enum for the outcome, severity REJECTED > CANCELLED >
    TRUNCATED > OK."""
    from repro.serving.engine import GenStats

    def resp(**kw):
        return Response(0, "", GenStats(), 0.0, **kw)

    assert resp().status is ResponseStatus.OK
    assert resp(truncated=True).status is ResponseStatus.TRUNCATED
    assert resp(truncated=True,
                cancelled=True).status is ResponseStatus.CANCELLED
    assert resp(cancelled=True,
                error="no").status is ResponseStatus.REJECTED


# ------------------------------------------------------------------ mesh


def _run_mesh_fault_parity(n_tokens=6):
    """Mesh column of the fault matrix: the macro engine on a fake host
    mesh under CHAOS weather must match the single-device per-token
    reference bit for bit (same counter-based weather, breaker carry
    pinned through the sharded scan)."""
    from repro.launch.mesh import make_serving_mesh
    scfg = get_config("floe-slm-2b").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm, llm = LM(scfg, remat=False), LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    parts_ = (slm, sp, llm, lp, mlp)
    assert n_tokens >= CHAOS_TRIP_TOKENS
    fault = FaultModel(**CHAOS)
    mesh = make_serving_mesh(min(len(jax.devices()), 8))
    ref, _ = _run_batched(_dep(parts_, fault=fault), 0, n_tokens)
    got, eng = _run_batched(_dep(parts_, fault=fault, mesh=mesh), 4,
                            n_tokens)
    _assert_bitexact(ref, got, fusion=False)
    assert eng.health_stats()["breaker_trips"] >= 1
    return ref


@multi
@pytest.mark.timeout(540)
def test_mesh_fault_parity():
    _run_mesh_fault_parity()


@pytest.mark.skipif(
    MULTI, reason="in-process mesh tests already run on this backend")
def test_mesh_fault_parity_subprocess():
    """Single-device tier-1 fallback: re-run the mesh fault parity in a
    fresh interpreter with 8 fake CPU devices (the device count is
    locked at first jax init)."""
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
    assert "FAULT-MESH-OK" in out.stdout


if __name__ == "__main__":
    assert len(jax.devices()) >= 4, "set XLA_FLAGS before running"
    _run_mesh_fault_parity()
    print("FAULT-MESH-OK")
