"""Time to first token, 90th percentile over every request due in the
window: due time -> end of the first boundary after which the host holds
the request's first token.  A request that failed or never finished
counts at the time the run stopped waiting for it (it misses any limit).
"""
import drive


def read(run):
    end = max(b.end for b in run.boundaries)
    vals = [((r.first if r.status == "ok" and r.first is not None else end)
             - r.due) * 1e3 for r in run.window_records()]
    return drive.percentile(vals, 90) if vals else None
