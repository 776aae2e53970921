"""Benchmark span around the session's construction, the first submit
(which builds the blocked view from the CSR) and the view's arrival on
the device."""


def read(run):
    spans = run.recorder.spans.get("view_build")
    return sum(spans) if spans else None
