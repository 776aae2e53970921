"""Milliseconds of `GraphSession.submit` per job: the program's span
`session.submit` (total over the window / its count)."""

from bench import span_digest


def read(run):
    return span_digest.span_ms_per_call("session.submit")
