"""The serving engine's own record: spans, counters, per-request times
and the intervals during which the engine holds work.

One ``Telemetry`` per engine (``engine.telemetry``), always on.  Every
time is ``time.perf_counter_ns()`` of the serving process.  Spans also
enter ``jax.profiler.TraceAnnotation`` under the same name, so a
profiler session, when one runs, shows them on the device trace's own
clock beside the programs they launched; without a session the
annotation records nothing.

The record is bounded by construction: the engine opens spans and bumps
counters once per lane boundary, per admission call or per prefill
chunk, never per token or per row, and the span store and the finished
requests are bounded deques.  Reading it is the caller's business:
``spans``, ``counters``, ``requests``, ``loaded`` and ``refused`` are
plain attributes.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass
from typing import Dict, Iterable, List, NamedTuple, Optional

import jax

clock_ns = time.perf_counter_ns

SPAN_CAP = 65_536        # several minutes of boundaries
DONE_CAP = 65_536        # finished requests kept in ``requests``


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    rid: Optional[int]


@dataclass
class RequestTimes:
    """Host times of one request: the first ``add_requests`` call that
    carried it, its admission, the boundary that emitted its first
    token, and its end (finished, cancelled or hard-rejected)."""
    offered: int
    admitted: Optional[int] = None
    first_token: Optional[int] = None
    done: Optional[int] = None


class _SpanScope:
    __slots__ = ("tel", "name", "rid", "sid", "parent", "t0", "ann")

    def __init__(self, tel: "Telemetry", name: str, rid: Optional[int]):
        self.tel, self.name, self.rid = tel, name, rid

    def __enter__(self):
        tel = self.tel
        self.parent = tel._open[-1] if tel._open else None
        tel._next_id += 1
        self.sid = tel._next_id
        tel._open.append(self.sid)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.t0 = clock_ns()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        t1 = clock_ns()
        tel = self.tel
        tel._open.pop()
        tel.spans.append(Span(self.name, self.t0, t1, self.sid,
                              self.parent, self.rid))
        return False


class Telemetry:
    """Spans, counters, request times and load of one engine."""

    def __init__(self, counters: Iterable[str] = (),
                 span_cap: int = SPAN_CAP, done_cap: int = DONE_CAP):
        self.spans: collections.deque = collections.deque(maxlen=span_cap)
        self.counters: Dict[str, int] = dict.fromkeys(counters, 0)
        self.requests: Dict[int, RequestTimes] = {}
        self._finished: collections.deque = collections.deque()
        self._done_cap = done_cap
        # [start_ns, end_ns or None while open]: the engine holds work
        self.loaded: collections.deque = collections.deque(maxlen=span_cap)
        # (t_ns, n): the soft-refused count of the latest add_requests
        # call, recorded whenever it changes
        self.refused: collections.deque = collections.deque(maxlen=span_cap)
        self._open: List[int] = []
        self._next_id = 0

    # ------------------------------------------------------------ spans
    def span(self, name: str, rid: Optional[int] = None) -> _SpanScope:
        """``with tel.span(name):`` records (name, start, end, id,
        enclosing span's id, rid) when the block ends."""
        return _SpanScope(self, name, rid)

    # --------------------------------------------------------- counters
    def declare(self, names: Iterable[str]):
        for n in names:
            self.counters.setdefault(n, 0)

    def add(self, name: str, n: int = 1):
        self.counters[name] = self.counters.get(name, 0) + n

    def merge(self, tally: Dict[str, int]):
        """Fold a caller's local tally in with one call (a boundary's
        or an admission call's worth of counts)."""
        c = self.counters
        for k, v in tally.items():
            c[k] = c.get(k, 0) + v

    def view(self, names: Iterable[str]) -> Dict[str, int]:
        return {k: self.counters.get(k, 0) for k in names}

    # --------------------------------------------------------- requests
    def offered(self, rids: Iterable[int], t: int):
        for rid in rids:
            r = self.requests.get(rid)
            if r is None or r.done is not None:     # new, or a reused rid
                self.requests[rid] = RequestTimes(t)

    def admitted(self, rids: Iterable[int], t: int):
        for rid in rids:
            r = self.requests.get(rid)
            if r is not None and r.admitted is None:
                r.admitted = t

    def first_token(self, rids: Iterable[int], t: int):
        for rid in rids:
            r = self.requests.get(rid)
            if r is not None and r.first_token is None:
                r.first_token = t

    def done(self, rids: Iterable[int], t: int):
        for rid in rids:
            r = self.requests.get(rid)
            if r is None or r.done is not None:
                continue
            r.done = t
            self._finished.append((rid, r))
        while len(self._finished) > self._done_cap:
            rid, r = self._finished.popleft()
            if self.requests.get(rid) is r:
                del self.requests[rid]

    # ------------------------------------------------------------- load
    @property
    def is_loaded(self) -> bool:
        return bool(self.loaded) and self.loaded[-1][1] is None

    def mark_load(self, loaded: bool, t: int):
        """Open a loaded interval at ``t`` if the engine took work, or
        close the open one if it let the last of it go."""
        if loaded and not self.is_loaded:
            self.loaded.append([t, None])
        elif not loaded and self.is_loaded:
            self.loaded[-1][1] = t

    def mark_refused(self, n: int, t: int):
        if (self.refused[-1][1] if self.refused else 0) != n:
            self.refused.append((t, n))
