"""Drive the engine's step loop with a traffic mix and time it.

The loop is the one ``ContinuousBatchScheduler.run`` runs
(``dispatch_step`` -> ``add_requests`` / ``pop_rejected`` ->
``collect_step``), with arrivals added: a request joins the queue at the
first boundary after its due time, and every latency is measured from
the due time on the host clock.  After each boundary the driver reads
the engine's per-row state (``GenStats.tokens`` of every occupied slot)
to see which requests have their first token on the host.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional


import arrivals as A

clock = time.perf_counter


@dataclass
class Record:
    """What the host saw of one request."""
    req: A.Request
    due: float                       # absolute host-clock due time
    admitted: Optional[float] = None
    first: Optional[float] = None
    done: Optional[float] = None
    tokens: int = 0
    token_ids: List[int] = field(default_factory=list)
    served_private: Optional[bool] = None
    cloud_tokens: int = 0
    status: str = "pending"          # ok | rejected | truncated | cancelled


@dataclass
class Boundary:
    start: float
    end: float
    tokens: int                      # tokens that reached the host
    emitted: list = field(default_factory=list)  # (rid, n0, n1, private)


class Driver:
    """One engine, one traffic mix; keeps every record and boundary."""

    def __init__(self, system, seed: int):
        self.sys, self.seed = system, seed
        self.eng = system.engine
        self.records: Dict[int, Record] = {}
        self.boundaries: List[Boundary] = []
        self.span = contextlib.nullcontext
        self.on_boundary: Optional[Callable[["Driver", Boundary], None]] \
            = None

    # ------------------------------------------------------------ steps
    def _submit(self, pending: List[Record], now: float):
        flags = self.eng.add_requests(
            [(r.req.prompt, r.req.max_new, True, r.req.rid)
             for r in pending])
        rejected = dict(self.eng.pop_rejected())
        still = []
        for r, ok in zip(pending, flags):
            if ok:
                r.admitted = now
            elif r.req.rid in rejected:
                r.status, r.done = "rejected", now
            else:
                still.append(r)
        return still

    def _seen(self, rid: int, stats, now: float, emitted: list):
        r = self.records.get(rid)
        if r is None:
            return 0
        new = stats.tokens - r.tokens
        if new > 0:
            emitted.append((rid, r.tokens, stats.tokens, stats.private))
        r.tokens = stats.tokens
        if r.first is None and stats.tokens > 0:
            r.first = now
        return new

    def boundary(self, pending: List[Record], due_now) -> List[Record]:
        """One macro boundary; returns the requests still waiting."""
        start = clock()
        with self.span("bench.dispatch"):
            self.eng.dispatch_step()
        pending = pending + due_now()
        if pending:
            with self.span("bench.admit"):
                pending = self._submit(pending, clock())
        with self.span("bench.collect"):
            finished = self.eng.collect_step()
        now = clock()
        got, emitted = 0, []
        for lane in self.sys.lanes():
            for s in list(lane.slots) + list(lane._evictq):
                if s is not None:
                    got += self._seen(s.rid, s.stats, now, emitted)
        for rid, _text, st in finished:
            got += self._seen(rid, st, now, emitted)
            r = self.records.get(rid)
            if r is None:
                continue
            r.done, r.token_ids = now, list(st.token_ids)
            r.served_private, r.cloud_tokens = st.private, st.cloud_tokens
            r.status = ("cancelled" if st.cancelled else
                        "truncated" if st.truncated else "ok")
        b = Boundary(start, now, got, emitted)
        self.boundaries.append(b)
        if self.on_boundary is not None:
            self.on_boundary(self, b)
        return pending


def due_between(records, lo: float, hi: float) -> List[Record]:
    return [r for r in records.values() if lo <= r.due < hi]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default), over all values given."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


# ---------------------------------------------------------------- warm-up
def _pow2_upto(n: int) -> List[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    out.append(b)
    return out


def _chunk_signature(length: int, width: int, page: int):
    """The chunked-prefill programs a prompt of ``length`` ids reaches:
    the middle chunks' history lengths and the final chunk's (history,
    padded width) — the shapes ``_admit_paged_chunked`` compiles."""
    pre, middles = width, []
    while length - pre > width:
        middles.append(pre)
        pre += width
    w = length - pre
    return tuple(middles), pre, -(-w // page) * page


def reachable_shapes(cfg: dict, mix: dict):
    """Per lane: the packed-prefill (rows, padded length) pairs and one
    prompt length per chunked-prefill signature that the mix can reach,
    evictions' re-admissions (prompt + tokens so far) included."""
    e = cfg["engine"]
    pt, ot = mix["prompt_tokens"], mix["output_tokens"]
    lo = pt["min"]
    hi = min(pt["max"] + ot["max"] - 1, e["max_ctx"] - 1)
    cw, pc = e["chunk_width"], e["prefill_chunk"]
    lpads = sorted({min(-(-n // pc) * pc, e["max_seq"])
                    for n in range(lo, min(hi, cw) + 1)})
    chunked = {}
    for n in range(max(lo, cw + 1), hi + 1):
        chunked.setdefault(_chunk_signature(n, cw, e["page_size"]), n)
    share = mix.get("private_share", 0.0)
    lanes = []
    if share < 1.0:
        lanes.append((False, e["cloud_rows"]))
    if share > 0.0:
        lanes.append((True, e["edge_rows"]))
    return lanes, lpads, sorted(chunked.values())


def warm_shapes(driver: "Driver", cfg: dict, mix: dict) -> int:
    """Compile (or load) and run every shape the cell's traffic can
    reach, lane by lane, with bursts of synthetic requests."""
    e = cfg["engine"]
    lanes, lpads, chunked = reachable_shapes(cfg, mix)
    rng = A._rng(driver.seed, 99)
    k, ps = e["macro_k"], e["page_size"]
    rid = [10_000_000]
    bursts = 0

    def burst(private: bool, lengths: List[int], max_new: int):
        reqs = []
        for n in lengths:
            reqs.append(A.Request(rid[0], 0.0,
                                  A.prompt_text(n, private, rng), n,
                                  max_new, private))
            rid[0] += 1
        pending = [Record(r, clock()) for r in reqs]
        for r in pending:
            driver.records[r.req.rid] = r
        while pending or driver.eng.active_count():
            pending = driver.boundary(pending, lambda: [])
        for r in reqs:
            rec = driver.records.pop(r.rid)
            if rec.status != "ok":
                raise RuntimeError(f"warm-up request of {r.prompt_tokens} "
                                   f"tokens ended {rec.status}")

    for private, rows in lanes:
        for bp in _pow2_upto(rows):
            for lpad in lpads:
                n = max(mix["prompt_tokens"]["min"],
                        lpad - e["prefill_chunk"] + 1)
                burst(private, [n] * min(bp, rows), k)
                bursts += 1
        for n in chunked:
            burst(private, [n], k)
            bursts += 1
        # every row crossing a page boundary at once: the growth path
        # (a prompt filling whole pages holds one decode page; the next
        # page is grown at a boundary)
        n = -(-mix["prompt_tokens"]["min"] // ps) * ps
        if n + ps + k <= e["max_ctx"]:
            for bp in _pow2_upto(rows):
                burst(private, [n] * min(bp, rows), ps + k)
                bursts += 1
    return bursts


# ----------------------------------------------------------- window plans
class Plan:
    """Warm-up traffic, window and drain of one mix, as one stream."""

    def __init__(self, mix: dict, cfg: dict, seed: int, seconds: float):
        self.mix, self.cfg, self.seed, self.seconds = mix, cfg, seed, seconds
        self.warm = float(mix["warmup_s"])
        self.state = None

    def trace_dir(self) -> str:
        import os
        return os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".bench_trace")

    def start(self, driver: Driver) -> float:
        """Serve the warm-up traffic; return the window's start time."""
        t_zero = clock()
        w0 = t_zero + self.warm
        segs = (A.open_segment(self.mix, self.seed, 0, 0.0, self.warm, 0)
                + A.open_segment(self.mix, self.seed, 1, self.warm,
                                 self.seconds, 1_000_000)
                + A.open_segment(self.mix, self.seed, 2,
                                 self.warm + self.seconds, 120.0, 2_000_000))
        self.state = OpenLoop(driver, segs, t_zero)
        self.state.serve(lambda now: now >= w0)
        return w0

    def window(self, driver: Driver, w1: float):
        self.state.serve(lambda now: now >= w1)

    def drain(self, driver: Driver, w0: float, w1: float, deadline: float):
        def done(now):
            if now >= deadline:
                return True
            return all(r.done is not None
                       for r in due_between(driver.records, w0, w1))
        self.state.serve(done)


class OpenLoop:
    def __init__(self, driver: Driver, reqs: List[A.Request], t_zero: float):
        self.d = driver
        self.reqs = sorted(reqs, key=lambda r: r.due)
        self.dues = [t_zero + r.due for r in self.reqs]
        self.nxt = 0
        self.pending: List[Record] = []

    def _due_now(self) -> List[Record]:
        now, out = clock(), []
        while self.nxt < len(self.reqs) and self.dues[self.nxt] <= now:
            r = self.reqs[self.nxt]
            rec = Record(r, self.dues[self.nxt])
            self.d.records[r.rid] = rec
            out.append(rec)
            self.nxt += 1
        return out

    def serve(self, stop):
        while not stop(clock()):
            self.pending = self.pending + self._due_now()
            if not self.pending and self.d.eng.active_count() == 0:
                if self.nxt >= len(self.reqs):
                    time.sleep(0.001)
                    continue
                time.sleep(max(0.0, min(self.dues[self.nxt] - clock(),
                                        0.01)))
                continue
            self.pending = self.d.boundary(self.pending, self._due_now)


# ------------------------------------------------------------- tracing
class TraceWindow:
    """Record the profiler's trace over ``seconds`` of the window,
    starting at the first boundary after ``begin``; host spans around
    the engine calls go into the same trace.  ``start_s`` and ``stop_s``
    are the host seconds the profiler's start and stop took: the
    window's traffic waits through both."""

    def __init__(self, driver: Driver, log_dir: str, begin: float,
                 seconds: float):
        import shutil
        self.d, self.dir = driver, log_dir
        self.begin, self.seconds = begin, seconds
        self.on = self.off = None
        self.start_s = self.stop_s = None
        self._span = None
        shutil.rmtree(log_dir, ignore_errors=True)
        driver.on_boundary = self._hook

    def _hook(self, d: Driver, b: Boundary):
        import jax
        if self.on is None and b.end >= self.begin:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            t = clock()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.start_s = clock() - t
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            d.span = jax.profiler.TraceAnnotation
            self.on = clock()
        elif self.on is not None and self.off is None \
                and b.end >= self.on + self.seconds:
            self._span.__exit__(None, None, None)
            self.off = clock()
            jax.profiler.stop_trace()
            self.stop_s = clock() - self.off
            d.span = contextlib.nullcontext
            d.on_boundary = None

    def close(self):
        if self.on is not None and self.off is None:
            self._hook(self.d, Boundary(0, float("inf"), 0))

    def reduce(self, programs):
        import devtrace
        self.close()
        if self.on is None:
            raise RuntimeError("the window ended before the trace began")
        dops = devtrace.reduce_xspace(devtrace.find_xspace(self.dir))
        dops.programs = devtrace.attribute(dops, programs.module_names())
        return dops

    def boundaries(self) -> List[Boundary]:
        return [b for b in self.d.boundaries
                if self.on <= b.start and b.end <= self.off]

    def breakdown(self, dops, programs) -> dict:
        """The ten costliest device operations (leaf ops, by entry point
        and op name) and the ten longest idle gaps of chip 0, each
        labelled by the host span it overlaps most."""
        import devtrace
        per = {}
        for o in dops.ops:
            name = devtrace.short(o.name)
            if devtrace.CONTAINER.match(name) or o.name == "busy":
                continue
            key = (f"{dops.programs.get(o.module, '?')}:"
                   f"{name}")
            per[key] = per.get(key, 0) + o.dur
        top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
        gaps = []
        for s, e in dops.idle_gaps(0):
            best, label = 0, "outside the engine calls"
            for name, hs, he in dops.spans:
                if name == "bench.traced":
                    continue
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best, label = ov, name
            gaps.append((label, (e - s) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, v * 1e-9 / dops.devices] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps[:10]]}
