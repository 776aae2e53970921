"""Milliseconds of `GraphSession.detach` per job: the program's span
`session.detach` (total over the window / its count), the result's
read-back included."""

from bench import span_digest


def read(run):
    return span_digest.span_ms_per_call("session.detach")
