"""Block pairs the schedule selected (`RunMetrics.tile_pair_loads`: the
pairs whose source block was staged) over all P pairs in every
superstep, in %."""


def read(run):
    steps = run.recorder.counters.get("supersteps", 0)
    if not steps:
        return None
    return (100.0 * run.recorder.counters["tile_pair_loads"]
            / (run.shapes["num_pairs"] * steps))
