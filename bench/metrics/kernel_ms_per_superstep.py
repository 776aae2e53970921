"""Device time of the fused superstep kernel in the traced window, in ms
per superstep."""

from bench import work


def read(run):
    steps = run.recorder.counters.get("supersteps", 0)
    if run.trace is None or not steps:
        return None
    t = work.kernel_seconds(run.trace["ops"])
    return 1e3 * t / steps if t > 0 else None
