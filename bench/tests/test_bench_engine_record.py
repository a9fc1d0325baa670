"""The readers of the engine's own record, on a synthetic run whose
numbers are worked by hand: the loaded idle share on the device trace's
clock, host time per lane boundary and the refused-count time average
before the profiler starts, and None where there is nothing to read."""
from collections import deque, namedtuple
from types import SimpleNamespace as NS

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import devtrace
import run

Span = namedtuple("Span", "name start_ns end_ns span_id parent_id rid")
ON = 5_000_000_000              # host ns at which the trace starts
MS = 1_000_000


def _run():
    # chip 0 busy [0, 10), [30, 40), [60, 100) on the trace's clock,
    # whose window [0, 100) starts at host ON
    ops = [devtrace.Op(0, "busy", s, e - s)
           for s, e in ((0, 10), (30, 40), (60, 100))]
    dops = devtrace.DeviceOps(ops, [], [("bench.traced", 0, 100)], 0, 100,
                              1)
    spans = [Span("floe.prepare", ON - 3_000 * MS, ON - 2_990 * MS, 1,
                  None, None)]                      # before w0: left out
    t = ON - 900 * MS
    for i, (prep, replay) in enumerate(((2, 1), (3, 4))):
        sid = 10 * (i + 1)
        spans += [Span("floe.prepare", t, t + prep * MS, sid, None, None),
                  Span("floe.readmit", t, t + MS, sid + 1, sid, None),
                  Span("floe.enqueue", t + prep * MS, t + (prep + 1) * MS,
                       sid + 2, None, None),
                  Span("floe.sync", t + 10 * MS, t + 20 * MS, sid + 3,
                       None, None),
                  Span("floe.replay", t + 20 * MS, t + (20 + replay) * MS,
                       sid + 4, None, None)]
        t += 100 * MS
    spans.append(Span("floe.replay", ON + MS, ON + 50 * MS, 99, None,
                      None))                        # after trace.on
    tel = NS(spans=deque(spans, maxlen=1000),
             loaded=deque([[ON - 50, ON + 20], [ON + 35, ON + 50],
                           [ON + 90, None]]),
             refused=deque([(ON - 1_500 * MS, 1), (ON - 800 * MS, 3),
                            (ON - 400 * MS, 0), (ON + 200 * MS, 2)]))
    trace = NS(on=ON * 1e-9, off=(ON + 100) * 1e-9,
               d=NS(eng=NS(telemetry=tel)))
    return run.Run(dops=dops, trace=trace, w0=(ON - 1_000 * MS) * 1e-9)


def test_readers_by_hand():
    r = _run()
    # loaded on the trace's clock: [0, 20], [35, 50], [90, 100] = 45 ns;
    # idle inside it: [10, 20] and [40, 50] = 20 ns
    assert run.reader("loaded_idle_share")(r) == pytest.approx(
        100 * 20 / 45)
    # two lane boundaries before the trace: (2 + 1) and (3 + 4) ms; the
    # readmit child lies inside its prepare and is not counted again
    assert run.reader("engine_host_ms_per_boundary")(r) == pytest.approx(
        5.0)
    # over [w0, on) = 1 s: 1 for 0.2 s, 3 for 0.4 s, 0 for 0.4 s
    assert run.reader("gate_queue_mean")(r) == pytest.approx(1.4)


@pytest.mark.parametrize("name", ["loaded_idle_share",
                                  "engine_host_ms_per_boundary",
                                  "gate_queue_mean"])
def test_nothing_to_read_gives_none(name):
    untraced = run.Run(dops=None, trace=None, w0=0.0)
    assert run.reader(name)(untraced) is None
    r = _run()
    r.trace.d.eng = NS()                  # an engine that keeps no record
    assert run.reader(name)(r) is None


def test_a_record_that_dropped_the_window_gives_none():
    r = _run()
    tel = r.trace.d.eng.telemetry
    tel.spans = deque(list(tel.spans)[1:], maxlen=len(tel.spans) - 1)
    tel.refused = deque(list(tel.refused)[2:], maxlen=2)
    assert run.reader("engine_host_ms_per_boundary")(r) is None
    assert run.reader("gate_queue_mean")(r) is None
