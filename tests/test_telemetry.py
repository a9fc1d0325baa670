"""The engine's record (serving/telemetry.py): request times in order,
spans and counter updates per boundary and per admission call only, the
loaded intervals and refused-count record of a one-row FIFO worked by
hand, and growth_stats()/health_stats() as views over the one counter
dict."""
from collections import Counter

import jax
import pytest

from repro.configs import get_config
from repro.core import fusion as FUS
from repro.models.model import LM
from repro.serving.deployment import ServingDeployment
from repro.serving.engine import (GROWTH_KEYS, HEALTH_KEYS,
                                  BatchedHybridEngine)
from repro.serving.latency import LatencyModel
from repro.serving.scheduler import ContinuousBatchScheduler
from repro.serving.telemetry import Telemetry

PROMPTS = ["hi there", "what is the capital of france",
           "my password is hunter2", "tell me a story about a long walk"]
DECODE = ("floe.prepare", "floe.readmit", "floe.provision",
          "floe.enqueue", "floe.sync", "floe.replay")


@pytest.fixture(scope="module")
def dep():
    scfg = get_config("floe-slm-2b").reduced()
    lcfg = get_config("floe-llm-7b").reduced()
    slm, llm = LM(scfg, remat=False), LM(lcfg, remat=False)
    sp, lp = slm.init(jax.random.key(0)), llm.init(jax.random.key(1))
    mlp = FUS.init_alignment(jax.random.key(2), scfg.vocab_size)
    return ServingDeployment(slm, sp, llm, lp, mlp,
                             latency=LatencyModel(rtt_ms=10, jitter_ms=0),
                             timeout_ms=200.0, max_seq=48, page_size=4)


class Calls:
    """Counts the engine's admission calls and counter updates."""

    def __init__(self, eng):
        self.admissions = self.updates = 0
        tel, add_requests = eng.telemetry, eng.add_requests
        add, merge = tel.add, tel.merge

        def counted(f, attr):
            def g(*a, **k):
                setattr(self, attr, getattr(self, attr) + 1)
                return f(*a, **k)
            return g
        eng.add_requests = counted(add_requests, "admissions")
        tel.add = counted(add, "updates")
        tel.merge = counted(merge, "updates")


def _serve(eng, reqs):
    calls = Calls(eng)
    sched = ContinuousBatchScheduler(eng)
    for p, n in reqs:
        sched.submit(p, n)
    out = sched.run()
    assert all(r.error is None for r in out)
    return out, calls


def test_request_times_in_order(dep):
    eng = BatchedHybridEngine(deployment=dep, batch_size=2,
                              edge_batch_size=1, macro_k=4)
    out, _ = _serve(eng, [(p, 6) for p in PROMPTS])
    rec = eng.telemetry.requests
    assert sorted(rec) == [r.rid for r in out]
    for r in rec.values():
        assert r.offered <= r.admitted <= r.first_token <= r.done


@pytest.mark.parametrize("macro_k,rows,max_new,spec_k", [
    (2, 1, 4, 0), (4, 2, 9, 0), (8, 2, 14, 0), (4, 2, 9, 2)])
def test_spans_and_counter_updates_per_boundary(dep, macro_k, rows,
                                                max_new, spec_k):
    """However many tokens, rows or steps a boundary holds, a lane
    boundary records the same six spans and two counter updates (page
    provisioning, replay; the speculative seed adds one), and an
    admission call two spans, one per lane prefill and one update."""
    eng = BatchedHybridEngine(deployment=dep, batch_size=rows,
                              edge_batch_size=1, macro_k=macro_k,
                              spec_k=spec_k)
    out, calls = _serve(eng, [(p, max_new) for p in PROMPTS])
    tel = eng.telemetry
    names = Counter(s.name for s in tel.spans)
    lanes = tel.counters["boundaries_cloud"] + tel.counters["boundaries_edge"]
    assert lanes > 0
    assert {n: names[n] for n in DECODE} == dict.fromkeys(DECODE, lanes)
    assert names["floe.admit"] == names["floe.gate"] == calls.admissions
    assert 0 < names["floe.prefill_packed"] <= 2 * calls.admissions
    seeds = tel.counters["boundaries_cloud"] if spec_k else 0
    assert calls.updates == 2 * lanes + seeds + calls.admissions
    assert sum(names.values()) == (len(DECODE) * lanes
                                   + 2 * calls.admissions
                                   + names["floe.prefill_packed"])
    c = tel.counters
    assert c["tokens_emitted"] == sum(r.stats.tokens for r in out)
    assert c["offers"] == c["admitted"] + c["hard_rejects"] + sum(
        c[k] for k in c if k.startswith("refused_"))
    assert c["admitted"] == len(PROMPTS)


def test_spans_nest_and_name_chunks(dep):
    """Chunked prefill: one floe.chunk per chunk under
    floe.prefill_chunked (which carries the rid), inside floe.admit;
    boundary children inside floe.prepare."""
    eng = BatchedHybridEngine(deployment=dep, batch_size=2,
                              edge_batch_size=1, macro_k=4, chunk_width=16)
    long = "a long question " * 2           # 33 ids: chunks 16, 16, 1
    out, _ = _serve(eng, [(long, 4), ("hi there", 4)])
    tel = eng.telemetry
    by_id = {s.span_id: s for s in tel.spans}

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None
    chunked = [s for s in tel.spans if s.name == "floe.prefill_chunked"]
    assert len(chunked) == 1 and chunked[0].rid == out[0].rid
    assert parent(chunked[0]) == "floe.admit"
    chunks = [s for s in tel.spans if s.name == "floe.chunk"]
    assert len(chunks) == 3 == tel.counters["prefill_chunks"]
    assert all(s.parent_id == chunked[0].span_id and s.rid == out[0].rid
               for s in chunks)
    for s in tel.spans:
        want = {"floe.readmit": "floe.prepare",
                "floe.provision": "floe.prepare",
                "floe.gate": "floe.admit"}.get(s.name)
        if want:
            assert parent(s) == want
        assert s.start_ns <= s.end_ns


def test_one_row_fifo_loaded_and_refused_by_hand(dep):
    """Three cloud requests offered at once to a one-row lane: the first
    is admitted and the other two wait (one on the full lane, one behind
    it), then one each time the row frees.  The engine is loaded from
    the first offer to the last completion, and the refused count steps
    2 -> 1 -> 0 at the second and third admissions."""
    eng = BatchedHybridEngine(deployment=dep, batch_size=1,
                              edge_batch_size=1, macro_k=4)
    prompts = ["hi there", "what is the capital of france",
               "tell me a story about a long walk"]
    out, _ = _serve(eng, [(p, 6) for p in prompts])
    tel = eng.telemetry
    a, b, c = (tel.requests[r.rid] for r in out)
    assert a.offered == b.offered == c.offered
    assert a.done <= b.admitted and b.done <= c.admitted
    assert list(tel.loaded) == [[a.offered, c.done]]
    assert list(tel.refused) == [(a.admitted, 2), (b.admitted, 1),
                                 (c.admitted, 0)]
    integral = 2 * (b.admitted - a.admitted) + (c.admitted - b.admitted)
    assert integral == sum(n * (t1 - t0) for (t0, n), (t1, _) in
                           zip(tel.refused, list(tel.refused)[1:]))
    assert tel.counters["refused_lane_full"] >= 2
    assert tel.counters["refused_fifo"] >= 1
    assert not tel.is_loaded


def test_stats_are_views_over_the_counters(dep):
    eng = BatchedHybridEngine(deployment=dep, batch_size=2,
                              edge_batch_size=1, macro_k=4)
    _serve(eng, [("hi there", 12)])
    assert tuple(eng.growth_stats()) == GROWTH_KEYS
    assert tuple(eng.health_stats()) == HEALTH_KEYS
    c = eng.telemetry.counters
    assert eng.growth_stats() == {k: c[k] for k in GROWTH_KEYS}
    assert eng.growth_stats()["grown_pages"] > 0
    c["parks"] += 5
    c["losses"] += 2
    assert eng.growth_stats()["parks"] == c["parks"]
    assert eng.health_stats()["losses"] == c["losses"]


def test_record_is_bounded():
    tel = Telemetry(span_cap=4, done_cap=2)
    for i in range(10):
        with tel.span("s", rid=i):
            pass
        tel.offered([i], i)
        tel.done([i], i + 1)
    assert [s.rid for s in tel.spans] == [6, 7, 8, 9]
    assert sorted(tel.requests) == [8, 9]
