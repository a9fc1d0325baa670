"""Requests waiting at the admission gate, time-averaged over the window
before the profiler starts: the number the engine's latest
``add_requests`` call refused softly (no free row, pages short, held
behind an earlier refusal)."""
import engine_record


def read(run):
    return engine_record.gate_queue_mean(run)
