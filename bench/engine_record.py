"""The serving engine's own record, read for the per-layer metrics of the
engine boundary and admission.

The engine keeps spans, counters, the intervals during which it holds
work, and the count of requests its latest admission call refused, all
on the host's ``perf_counter_ns`` (``engine.telemetry``, the program's
``serving/telemetry.py``).  Readers reach it through
``run.trace.d.eng``; on an untraced run, or where the engine keeps no
such record, every reading is None.

Host times map onto the device trace's clock through one anchor: the
``bench.traced`` span starts at ``dops.t0`` on the trace's clock where
``run.trace.on`` is on the host's.  The readings that need no device
trace take the part of the window before the profiler starts,
``[w0, trace.on)``, which its start and stop do not disturb.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

BOUNDARY_HOST = ("floe.prepare", "floe.replay")


def record(run):
    """The engine's recorder, or None."""
    trace = getattr(run, "trace", None)
    eng = getattr(getattr(trace, "d", None), "eng", None)
    return getattr(eng, "telemetry", None)


def host_ns(t_s: float) -> int:
    return round(t_s * 1e9)


def to_trace_ns(run, t_ns: int) -> int:
    return run.dops.t0 + t_ns - host_ns(run.trace.on)


def _holds_since(store, lo_ns: int, time_of) -> bool:
    """The store has dropped nothing recorded after ``lo_ns``: it never
    filled its bound, or its oldest entry is no later."""
    cap = getattr(store, "maxlen", None)
    return (not store or cap is None or len(store) < cap
            or time_of(store[0]) <= lo_ns)


def loaded_intervals(tel, lo_ns: int, hi_ns: int) -> List[Tuple[int, int]]:
    """The engine's loaded intervals clipped to [lo_ns, hi_ns] (host)."""
    out = []
    for s, e in tel.loaded:
        s, e = max(s, lo_ns), min(hi_ns if e is None else e, hi_ns)
        if e > s:
            out.append((s, e))
    return out


def overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Total length of the intersection of two sorted, disjoint
    interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def loaded_idle_share(run) -> Optional[float]:
    """Percent of the traced window's loaded time in which chip 0 runs
    no op."""
    tel = record(run)
    if tel is None or getattr(run, "dops", None) is None:
        return None
    d = run.dops
    load = [(max(to_trace_ns(run, s), d.t0), min(to_trace_ns(run, e), d.t1))
            for s, e in loaded_intervals(tel, host_ns(run.trace.on),
                                         host_ns(run.trace.off))]
    load = [(s, e) for s, e in load if e > s]
    total = sum(e - s for s, e in load)
    if not total:
        return None
    return 100.0 * overlap_ns(load, d.idle_gaps(0)) / total


def host_ms_per_boundary(run) -> Optional[float]:
    """Mean host milliseconds of ``floe.prepare`` + ``floe.replay`` per
    lane boundary (one ``floe.enqueue`` each) over [w0, trace.on)."""
    tel = record(run)
    if tel is None:
        return None
    lo, hi = host_ns(run.w0), host_ns(run.trace.on)
    if not _holds_since(tel.spans, lo, lambda s: s.start_ns):
        return None
    spans = [s for s in tel.spans if lo <= s.start_ns < hi]
    n = sum(s.name == "floe.enqueue" for s in spans)
    if not n:
        return None
    t = sum(s.end_ns - s.start_ns for s in spans if s.name in BOUNDARY_HOST)
    return t / n * 1e-6


def gate_queue_mean(run) -> Optional[float]:
    """Time-average over [w0, trace.on) of the number of requests the
    engine's latest ``add_requests`` call refused softly."""
    tel = record(run)
    if tel is None:
        return None
    lo, hi = host_ns(run.w0), host_ns(run.trace.on)
    if hi <= lo or not _holds_since(tel.refused, lo, lambda r: r[0]):
        return None
    n, t, area = 0, lo, 0
    for tt, k in tel.refused:
        if tt <= lo:
            n = k
            continue
        if tt >= hi:
            break
        area += n * (tt - t)
        t, n = tt, k
    area += n * (hi - t)
    return area / (hi - lo)
