"""95th percentile of due time to `GraphSession.submit` (benchmark
timestamps), over the requests admitted."""

from bench.stats import percentile


def read(run):
    waits = [q.submit_s - q.due_s for q in run.queries
             if q.submit_s is not None]
    return percentile(waits, 95) if waits else None
