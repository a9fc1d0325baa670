"""Time per output token after the first, 90th percentile over every
request due in the window: (last token - first token) / (tokens - 1) on
the host clock.  A request that failed or never finished counts as the
whole wait from its due time; one that ended after a single token (an
early end of sequence) has no gap and is left out."""
import drive


def read(run):
    end = max(b.end for b in run.boundaries)
    vals = []
    for r in run.window_records():
        if r.status != "ok" or r.first is None:
            vals.append((end - r.due) * 1e3)
        elif r.tokens > 1:
            vals.append((r.done - r.first) / (r.tokens - 1) * 1e3)
    return drive.percentile(vals, 90) if vals else None
