"""Profiler capture and the reduction from a device trace to numbers.

A TPU trace has, per chip, a plane ``/device:TPU:n`` whose line "XLA
Modules" holds one event per program execution, named
``<module>(<id>)``, and whose line "XLA Ops" holds every op the program
ran (nested: a ``while`` op spans its body's ops).  Ops carry no program
id, so each is given to the module execution whose interval holds it.

Several deployment entry points are jitted under the same name (``impl``,
``<lambda>``), so a module's name cannot say which entry point it came
from.  ``Programs`` wraps every entry point named in ``programs.json``:
each call runs inside a host span ``bench.call:<entry>`` on the trace's
own clock, and the first call of each argument signature asks JAX which
module name that compiles to.  Executions of one module name run in the
order their calls were made (one device queue), so ``attribute`` lines
the executions of each module name up with the calls that launched them
and gives each module id the entry point most of its executions line up
with.

``DeviceOps`` is what the metric readers see; ``compact`` keeps the
union of op intervals and only the ops a reader names, so a recorded
trace fits in ``fixtures/`` and reproduces the readers' numbers.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
CALL_SPAN = "bench.call:"


def short(op_text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12``."""
    return op_text.split(" =", 1)[0].lstrip("%")


@dataclass
class Op:
    device: int
    name: str           # the op's HLO text as the trace names it
    start: int          # ns on the trace clock
    dur: int
    module: Optional[int] = None   # id of the module execution's program

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Module:
    device: int
    name: str           # e.g. "jit_impl"
    id: int
    start: int
    dur: int


@dataclass
class DeviceOps:
    ops: List[Op]
    modules: List[Module]
    spans: List[Tuple[str, int, int]]       # host spans (name, start, end)
    t0: int                                 # traced window, ns
    t1: int
    devices: int
    programs: Dict[int, str] = field(default_factory=dict)  # id -> entry

    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def entry_seconds(self, entries) -> float:
        """Device seconds of the executions of these entry points, per
        chip (module executions on one chip never overlap)."""
        want = set(entries)
        return sum(m.dur for m in self.modules
                   if self.programs.get(m.id) in want) * 1e-9 / self.devices

    def ops_of(self, entries) -> List[Op]:
        want = set(entries)
        return [o for o in self.ops if self.programs.get(o.module) in want]

    def busy_s(self) -> float:
        """Union of op intervals inside the window, averaged over chips."""
        total = 0
        for d in range(self.devices):
            for s, e in self._union(d):
                total += e - s
        return total * 1e-9 / self.devices

    def _union(self, device: int) -> List[Tuple[int, int]]:
        iv = sorted((max(o.start, self.t0), min(o.end, self.t1))
                    for o in self.ops if o.device == device)
        out: List[List[int]] = []
        for s, e in iv:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    def idle_gaps(self, device: int = 0) -> List[Tuple[int, int]]:
        gaps, end = [], self.t0
        for s, e in self._union(device):
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if self.t1 > end:
            gaps.append((end, self.t1))
        return gaps

    def compact(self, keep) -> "DeviceOps":
        """The same readings from fewer events: each chip's busy
        intervals as ops named "busy", plus the ops ``keep(op)`` wants."""
        ops = [Op(d, "busy", s, e - s) for d in range(self.devices)
               for s, e in self._union(d)]
        ops += [o for o in self.ops if keep(o)]
        return DeviceOps(ops, self.modules, self.spans, self.t0, self.t1,
                         self.devices, dict(self.programs))

    def to_json(self) -> dict:
        names = sorted({o.name for o in self.ops})
        index = {n: i for i, n in enumerate(names)}
        return {"t0": self.t0, "t1": self.t1, "devices": self.devices,
                "programs": {str(k): v for k, v in self.programs.items()},
                "spans": self.spans, "names": names,
                "ops": [[o.device, index[o.name], o.start, o.dur, o.module]
                        for o in self.ops],
                "modules": [[m.device, m.name, str(m.id), m.start, m.dur]
                            for m in self.modules]}

    @classmethod
    def from_json(cls, d: dict) -> "DeviceOps":
        names = d["names"]
        return cls([Op(o[0], names[o[1]], o[2], o[3], o[4])
                    for o in d["ops"]],
                   [Module(m[0], m[1], int(m[2]), m[3], m[4])
                    for m in d["modules"]],
                   [tuple(s) for s in d["spans"]], d["t0"], d["t1"],
                   d["devices"], {int(k): v
                                  for k, v in d["programs"].items()})


MODULE_NAME = re.compile(r"^(.*)\((\d+)\)$")


def reduce_xspace(path: str) -> DeviceOps:
    """Device modules and ops of every ``/device:TPU:n`` plane and the
    benchmark's host spans (``bench.*``) of an ``.xplane.pb``; the
    window is the host span ``bench.traced``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Module] = []
    spans: List[Tuple[str, int, int]] = []
    devices = set()
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                dev = int(m.group(1))
                devices.add(dev)
                ops.extend(Op(dev, ev.name, int(ev.start_ns),
                              int(ev.duration_ns)) for ev in line.events)
            elif m and line.name == "XLA Modules":
                dev = int(m.group(1))
                for ev in line.events:
                    mm = MODULE_NAME.match(ev.name)
                    if mm:
                        modules.append(Module(dev, mm.group(1),
                                              int(mm.group(2)),
                                              int(ev.start_ns),
                                              int(ev.duration_ns)))
            elif not m and plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    window = [(s, e) for n, s, e in spans if n == "bench.traced"]
    if not ops or not window:
        raise RuntimeError(f"{path}: no device ops or no bench.traced span")
    _assign_modules(ops, modules)
    return DeviceOps(ops, modules, sorted(spans, key=lambda s: s[1]),
                     window[0][0], window[0][1], len(devices))


def _assign_modules(ops: List[Op], modules: List[Module]):
    """Give each op the id of the module execution that holds it."""
    by_dev = defaultdict(list)
    for m in modules:
        by_dev[m.device].append(m)
    for d in by_dev:
        by_dev[d].sort(key=lambda m: m.start)
    starts = {d: [m.start for m in ms] for d, ms in by_dev.items()}
    for o in ops:
        ms = by_dev.get(o.device)
        if not ms:
            continue
        i = bisect.bisect_right(starts[o.device], o.start) - 1
        if i >= 0 and o.start < ms[i].start + ms[i].dur:
            o.module = ms[i].id


def attribute(dops: DeviceOps, module_names: Dict[str, set],
              skew_ns: int = 5_000_000) -> Dict[int, str]:
    """Module id -> entry point.  ``module_names`` maps each entry point
    to the module names its calls compile to.  Per module name, the
    executions (chip 0, in time order) and the calls (host spans
    ``bench.call:<entry>``, in order) are lined up: executions of one
    name run in call order, and the first few may come from calls made
    before the window began.  Of the offsets at which no execution
    starts more than ``skew_ns`` before its call (the host and device
    clocks of a trace differ by a fraction of a millisecond), the one
    whose pairs give each module id the most consistent entry point is
    taken; each id then goes to its most frequent entry point."""
    calls = [(s, n[len(CALL_SPAN):]) for n, s, _ in dops.spans
             if n.startswith(CALL_SPAN)]
    out: Dict[int, str] = {}
    for name in sorted({n for ns in module_names.values() for n in ns}):
        cs = sorted((s, e) for s, e in calls
                    if name in module_names.get(e, ()))
        ms = sorted((m for m in dops.modules
                     if m.device == 0 and m.name == name),
                    key=lambda m: m.start)
        best = None
        for off in range(-len(ms) + 1, len(cs)):
            pairs = [(ms[i], cs[i + off]) for i in range(len(ms))
                     if 0 <= i + off < len(cs)]
            if not pairs or any(m.start < c[0] - skew_ns
                                for m, c in pairs):
                continue
            votes: Dict[int, Counter] = defaultdict(Counter)
            for m, c in pairs:
                votes[m.id][c[1]] += 1
            agree = sum(v.most_common(1)[0][1] for v in votes.values())
            score = (agree - (len(pairs) - agree), len(pairs))
            if best is None or score > best[0]:
                best = (score, votes)
        if best is not None:
            for mid, v in best[1].items():
                out[mid] = v.most_common(1)[0][0]
    return out


def find_xspace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no profile under {log_dir}")
    return files[-1]


class Programs:
    """Entry-point recorders: a host span per call, the argument
    signatures seen (for their module names), and (host time, entry) of
    every call.  Nothing here waits for the device."""

    def __init__(self, dep, table: Dict[str, List[str]]):
        self.dep, self.table = dep, table
        self.seen: Dict[str, Dict[str, tuple]] = defaultdict(dict)
        self.log: List[Tuple[float, str]] = []
        self.missing = []
        for entries in table.values():
            for name in entries:
                fn = getattr(dep, name, None)
                if fn is None:
                    self.missing.append(name)
                    continue
                setattr(dep, name, self._wrap(name, fn))

    def _wrap(self, name, fn):
        import time
        import jax

        def spec(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None)
            return x

        def recording(*args):
            key = repr(jax.tree.map(
                lambda x: (getattr(x, "shape", x), str(getattr(x, "dtype",
                                                               ""))), args))
            if key not in self.seen[name]:
                self.seen[name][key] = jax.tree.map(spec, args)
            self.log.append((time.perf_counter(), name))
            with jax.profiler.TraceAnnotation(CALL_SPAN + name):
                return fn(*args)
        recording.inner = fn
        return recording

    def module_names(self) -> Dict[str, set]:
        """Entry point -> the module names its calls compiled to (asked
        after the window: lowering is host work the window must not do)."""
        out: Dict[str, set] = defaultdict(set)
        for name, sigs in self.seen.items():
            fn = getattr(self.dep, name).inner
            for specs in sigs.values():
                out[name].add(module_name(fn.lower(*specs)))
        return out


def module_name(lowered) -> str:
    """The HLO module name a lowered jit compiles to (``jit_<fn>``)."""
    text = lowered.as_text()
    m = re.search(r"module @(\S+)", text)
    if not m:
        raise RuntimeError("no module name in the lowered program")
    return m.group(1)
