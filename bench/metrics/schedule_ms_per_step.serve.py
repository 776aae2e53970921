"""Milliseconds of the serve front's two-level admission per call: the
program's span `serve.schedule` (self time over the window / its count)."""

from bench import span_digest


def read(run):
    return span_digest.span_ms_per_call("serve.schedule", "self_s")
