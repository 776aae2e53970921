"""Program spans and counters: the JAX profiler's host trace, a digest of
the profiled interval, and Chrome/Perfetto trace-event JSON.

``span(name, recorder=None, **args)`` is the program's one span path:

  profiler  while a JAX profiler trace is live
            (``jax.profiler.TraceAnnotation.is_enabled()``), the span is a
            ``TraceAnnotation`` named ``repro.<name>`` carrying ``args``:
            it lands on the host plane of the ``.xplane.pb``, on the same
            clock as the device's ops, nested in whatever annotation
            encloses it.
  digest    while a profiler trace is live, each span also adds to a
            module-level digest: per name its count, total seconds, self
            seconds (total less the time of the spans nested in it on the
            same thread) and longest seconds; ``count(name, n)`` adds to a
            counter there.  ``digest()`` returns a copy, ``reset_digest()``
            clears it.  With no profiler live, neither a span nor a
            counter records anything (one flag read), so the digest covers
            exactly the profiled interval.
  recorder  an enabled ``TraceRecorder`` (a session built with
            ``telemetry=``) also gets a complete event (ph="X") on its own
            clock.

A ``TraceRecorder`` collects the discrete story of a session — job
submit/detach, run and superstep spans, apply_updates batches, overlay
compactions, serve admissions — as Trace Event Format records
(https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):

  ph="X"  complete span (ts + dur)
  ph="i"  instant event
  ph="C"  counter track (per-superstep telemetry series)
  ph="M"  metadata (process/thread names, emitted at export)

``export(path)`` writes ``{"traceEvents": [...]}`` — loadable in
chrome://tracing and https://ui.perfetto.dev as-is.  Timestamps are
microseconds on a perf_counter clock anchored at recorder creation.

Recording is gated on ``enabled`` so telemetry-off sessions pay nothing
for it; a disabled recorder's export writes an empty-but-valid trace.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["TraceRecorder", "validate_trace_events", "span", "count",
           "digest", "reset_digest"]

# phases this recorder emits (export-time schema guarantee)
_PHASES = ("X", "i", "C", "M")

REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")

_live = TraceAnnotation.is_enabled

# the digest of the profiled interval: per span name [count, total_s,
# self_s, max_s]; per counter its sum
_spans: Dict[str, list] = {}
_counters: Dict[str, float] = {}
# per thread, the child seconds of each open digest span, innermost last
_stack = threading.local()
_digest_lock = threading.Lock()


class _Span:
    """What `span` returns; `set(**args)` adds args known only inside."""

    __slots__ = ("name", "recorder", "cat", "tid", "args", "_ann", "_t0",
                 "_us0", "_kids")

    def __init__(self, name, recorder, cat, tid, args):
        self.name, self.recorder, self.cat, self.tid, self.args = (
            name, recorder, cat, tid, args)
        self._ann = self._us0 = None

    def __enter__(self):
        rec = self.recorder
        if rec is not None and rec.enabled:
            self._us0 = rec.now_us()
        if _live():
            self._ann = TraceAnnotation(f"repro.{self.name}", **self.args)
            self._ann.__enter__()
            kids = getattr(_stack, "kids", None)
            if kids is None:
                kids = _stack.kids = []
            kids.append(0.0)
            self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        if self._ann is not None:
            dur = time.perf_counter() - self._t0
            self._ann.__exit__(*exc)
            kids = _stack.kids
            own = dur - kids.pop()
            if kids:
                kids[-1] += dur
            with _digest_lock:
                d = _spans.get(self.name)
                if d is None:
                    _spans[self.name] = [1, dur, own, dur]
                else:
                    d[0] += 1
                    d[1] += dur
                    d[2] += own
                    d[3] = max(d[3], dur)
        if self._us0 is not None:
            self.recorder.complete(self.name, self._us0,
                                   self.recorder.now_us() - self._us0,
                                   cat=self.cat, tid=self.tid, **self.args)
        return False


def span(name: str, recorder: Optional["TraceRecorder"] = None, *,
         cat: str = "session", tid: int = 1, **args) -> _Span:
    """Context manager around one piece of program work (module
    docstring); `cat` and `tid` place it on the recorder's tracks."""
    return _Span(name, recorder, cat, tid, args)


def count(name: str, n: float) -> None:
    """Add `n` to the digest's counter `name` while a profiler trace is
    live."""
    if _live():
        with _digest_lock:
            _counters[name] = _counters.get(name, 0) + n


def digest() -> dict:
    """A copy of the digest: {"spans": {name: {"count", "total_s",
    "self_s", "max_s"}}, "counters": {name: sum}}."""
    with _digest_lock:
        return {"spans": {k: dict(zip(("count", "total_s", "self_s",
                                       "max_s"), v))
                          for k, v in _spans.items()},
                "counters": dict(_counters)}


def reset_digest() -> None:
    with _digest_lock:
        _spans.clear()
        _counters.clear()


class TraceRecorder:
    """Append-only trace-event collector with a session-local clock."""

    def __init__(self, enabled: bool = True, *, pid: int = 1):
        self.enabled = enabled
        self.pid = pid
        self.events: List[dict] = []
        self._t0 = time.perf_counter()
        self._thread_names: Dict[int, str] = {1: "session"}

    # -- clock ---------------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since recorder creation (the trace timebase)."""
        return (time.perf_counter() - self._t0) * 1e6

    # -- event emitters ------------------------------------------------------

    def _emit(self, ev: dict) -> None:
        self.events.append(ev)

    def instant(self, name: str, cat: str = "session",
                ts_us: Optional[float] = None, tid: int = 1, **args) -> None:
        """One instant event (ph='i'), e.g. a job submit or a compaction."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": self.now_us() if ts_us is None else ts_us,
                    "pid": self.pid, "tid": tid, "args": args})

    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "session", tid: int = 1, **args) -> None:
        """A finished span (ph='X') with explicit start/duration."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "X", "ts": ts_us,
                    "dur": max(dur_us, 0.0), "pid": self.pid, "tid": tid,
                    "args": args})

    def span(self, name: str, cat: str = "session", tid: int = 1,
             **args) -> _Span:
        """`span(name, recorder=self, ...)`: one complete event around the
        body, and the profiler annotation while a trace is live."""
        return span(name, self, cat=cat, tid=tid, **args)

    def counter(self, name: str, values: Dict[str, float],
                ts_us: Optional[float] = None, cat: str = "telemetry",
                tid: int = 1) -> None:
        """One counter sample (ph='C'); each key renders as a track."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "C",
                    "ts": self.now_us() if ts_us is None else ts_us,
                    "pid": self.pid, "tid": tid,
                    "args": {k: float(v) for k, v in values.items()}})

    def name_thread(self, tid: int, name: str) -> None:
        self._thread_names[tid] = name

    # -- export --------------------------------------------------------------

    def _metadata(self) -> List[dict]:
        meta = [{"name": "process_name", "ph": "M", "ts": 0.0, "pid": self.pid,
                 "tid": 1, "args": {"name": "repro.GraphSession"}}]
        for tid, name in sorted(self._thread_names.items()):
            meta.append({"name": "thread_name", "ph": "M", "ts": 0.0,
                         "pid": self.pid, "tid": tid, "args": {"name": name}})
        return meta

    def to_json(self) -> dict:
        # ts-sorted: chrome://tracing tolerates disorder, Perfetto's JSON
        # importer is stricter about counter tracks
        events = self._metadata() + sorted(self.events,
                                           key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace-event JSON file; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
        return path

    def clear(self) -> None:
        self.events.clear()


def validate_trace_events(doc: dict) -> int:
    """Schema-check an exported trace document; returns the event count.

    Raises ValueError on the first malformed event — used by tests and the
    fig_trace benchmark to prove the export loads in Chrome/Perfetto.
    """
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace document must have a traceEvents list")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    for i, ev in enumerate(events):
        for k in REQUIRED_KEYS:
            if k not in ev:
                raise ValueError(f"event {i} missing key {k!r}: {ev}")
        if ev["ph"] not in _PHASES:
            raise ValueError(f"event {i} has unknown phase {ev['ph']!r}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"complete event {i} missing dur: {ev}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            raise ValueError(f"event {i} has invalid ts: {ev['ts']!r}")
    return len(events)
