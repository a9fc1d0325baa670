"""Share of the engine's loaded time in the traced window in which chip
0 runs no op: the engine holds an active or evicted row, or requests its
latest admission call refused, and the device waits on the host.  Time
with no work at all (the traffic's own gaps) is left out."""
import engine_record


def read(run):
    return engine_record.loaded_idle_share(run)
