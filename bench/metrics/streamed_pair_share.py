"""Block pairs the push streamed (the program's counter `pairs_streamed`)
over all P pairs in every superstep, in %: beside `selected_pair_share`,
what the kernel reads against what the schedule selected."""

from bench import span_digest


def read(run):
    streamed = span_digest.counter("pairs_streamed")
    steps = run.recorder.counters.get("supersteps", 0)
    if streamed is None or not steps:
        return None
    return 100.0 * streamed / (run.shapes["num_pairs"] * steps)
