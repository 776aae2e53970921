"""Blocking device-to-host reads in the window (the program's counter
`device_reads`) per completed query."""

from bench import span_digest


def read(run):
    reads = span_digest.counter("device_reads")
    done = len(run.completed)
    return reads / done if reads is not None and done else None
