"""`RunMetrics.supersteps` per converged batch, averaged over the
window's batches."""


def read(run):
    steps = [b["supersteps"] for b in run.recorder.batches
             if b["converged"]]
    return sum(steps) / len(steps) if steps else None
