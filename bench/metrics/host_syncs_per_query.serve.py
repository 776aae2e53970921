"""`RunMetrics.host_syncs` in the window per completed query."""


def read(run):
    done = len(run.completed)
    return run.recorder.counters.get("host_syncs", 0) / done if done else None
