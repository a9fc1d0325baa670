"""Host milliseconds a lane boundary spends around its decode, from the
engine's own spans: ``floe.prepare`` (deadline cancels, re-admission,
page growth, input arrays) plus ``floe.replay`` (trace replay into the
request slots, page releases), mean over lane boundaries in the window
before the profiler starts."""
import engine_record


def read(run):
    return engine_record.host_ms_per_boundary(run)
