"""The reduction from a device trace to per-layer numbers, replayed on a
trace recorded on the chip (``fixtures/``): the readers must give the
numbers that run printed."""
import gzip
import json
from types import SimpleNamespace as NS

import pytest

import tiny
import devtrace
import drive
import run

FIXTURES = sorted((tiny.BENCH / "fixtures").glob("trace_*.json.gz"))


def _replay(path):
    with gzip.open(path, "rt") as f:
        fx = json.load(f)
    dops = devtrace.DeviceOps.from_json(fx["dops"])
    bounds = [drive.Boundary(*b[:3], [tuple(e) for e in b[3]])
              for b in fx["boundaries"]]
    trace = NS(on=fx["on"], off=fx["off"], boundaries=lambda: bounds)
    records = {int(r): NS(req=NS(prompt_tokens=n), admitted=t)
               for r, (n, t) in fx["records"].items()}
    r = run.Run(dops=dops, trace=trace, records=records,
                programs=NS(table=fx["table"],
                            log=[tuple(c) for c in fx["log"]]),
                cfg=fx["cfg"], peak=fx["peak"], chips=fx["chips"])
    return r, fx["metrics"]


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_readers_reproduce_the_recorded_numbers(path):
    r, want = _replay(path)
    assert want
    for name, value in want.items():
        got = run.reader(name)(r)
        assert got == pytest.approx(value, rel=1e-9), name


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_shares_stay_within_their_range(path):
    r, want = _replay(path)
    for name, value in want.items():
        if name.endswith("_roofline") or "mfu" in name or \
                name.endswith("_share"):
            assert 0.0 < value <= 100.0, name


def _toy():
    mods = [devtrace.Module(0, "jit_impl", 7, 0, 20),
            devtrace.Module(0, "jit_impl", 9, 25, 20),
            devtrace.Module(1, "jit_impl", 7, 0, 45)]
    ops = [devtrace.Op(0, "%a = f()", 0, 10), devtrace.Op(0, "%b = g()", 5, 10),
           devtrace.Op(0, "%c = h()", 30, 10), devtrace.Op(1, "%a = f()", 0, 40)]
    devtrace._assign_modules(ops, mods)
    spans = [("bench.traced", 0, 50), ("bench.call:x", -3, -2),
             ("bench.call:y", 24, 25)]
    return devtrace.DeviceOps(ops, mods, spans, 0, 50, 2)


def test_busy_union_and_modules_by_hand():
    d = _toy()
    # chip 0: [0, 15) and [30, 40) = 25 ns; chip 1: 40 ns
    assert d.busy_s() == pytest.approx(32.5e-9)
    assert d.idle_gaps(0) == [(15, 30), (40, 50)]
    assert [o.module for o in d.ops] == [7, 7, 9, 7]
    d.programs = devtrace.attribute(d, {"x": {"jit_impl"},
                                        "y": {"jit_impl"}})
    assert d.programs == {7: "x", 9: "y"}
    assert [o.name for o in d.ops_of(["y"])] == ["%c = h()"]
    assert d.entry_seconds(["x"]) == pytest.approx((20 + 45) * 1e-9 / 2)
    small = d.compact(lambda o: o.name.startswith("%c"))
    assert small.busy_s() == d.busy_s()
    assert small.idle_gaps(0) == d.idle_gaps(0)
