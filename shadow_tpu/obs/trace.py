"""Span tracing: a low-overhead recorder emitting Chrome trace-event
JSON (viewable in Perfetto / chrome://tracing), with every span also on
the profiler's clock.

The reference self-times its scheduler barriers and per-host exec
seconds (shd-scheduler.c:250-252, shd-host.c:201-208) but only as
end-of-run aggregates; tools/phase_profile.py and xplane_profile.py
measure phases offline. This module is the ALWAYS-AVAILABLE in-run
counterpart: named wall-clock spans recorded on the host side (the
device reports through counters, not strings), serialized once at the
end of the run as one JSON timeline. Each window-chunk span carries its
sim-time range, windows advanced and events executed in `args`, so
sim-time progress and wall-clock cost correlate in a single view —
"where does the wall time go" answered per chunk, not per run.

Two sinks, one set of spans:

- the JSON timeline (``--trace FILE``, ``obs.perf``,
  ``tools/trace_report.py``): microseconds from tracer creation on
  ``perf_counter``;
- the profiler: a span opened with :meth:`Tracer.begin` is also a
  ``jax.profiler.TraceAnnotation`` (a TraceMe) of the same name and
  args, so whenever a ``jax.profiler`` session is active it lands in
  the xspace's host plane, on the device trace's clock, beside the
  device ops it waits on. With no session active a TraceMe records
  nothing.

Design constraints:

- Cheap when disabled. `ENABLED` is a module-level boolean; hot paths
  (the per-chunk loop in engine.sim) guard every hook with a plain
  ``if trace.ENABLED:`` so a run without a tracer pays one boolean
  check per hook and allocates nothing — no annotation either. The
  ``span()`` context manager and the module-level ``begin``/``end``
  are for cold paths only (setup, teardown, tools): they cost a call
  even when disabled.
- Cheap when enabled. A span is two perf_counter_ns reads, one TraceMe
  (about a microsecond, nothing recorded outside a profiler session)
  and one list append of a small dict; serialization happens once, at
  flush. A hard cap (MAX_EVENTS) bounds memory on runaway loops; the
  drop count is recorded in the trace metadata.
- One global tracer. Spans originate from several modules (engine,
  hosting, parallel, obs) on one thread of control; a process-global
  instance keeps the call sites to one import and one boolean.
  :attr:`Tracer.tags` holds args every span begun in its scope
  carries (the run loop sets ``run`` and ``chunk``), so spans opened
  deep in a callee (``digest.pull``) share the run's identifiers.

Timeline format: complete events (``"ph": "X"``) with microsecond
``ts``/``dur`` relative to tracer creation, wrapped as
``{"traceEvents": [...]}`` — both Perfetto and chrome://tracing load
this directly (catapult TraceEvent format).

Usage:

    from shadow_tpu.obs import trace
    trace.install("out.json")             # None: in memory only
    with trace.span("build"):             # cold path
        ...
    if trace.ENABLED:                     # hot path
        tok = trace.TRACER.begin("chunk", sim_ns_start=t)
    ...work...
    if trace.ENABLED:
        trace.TRACER.end(tok, windows=8)  # args known at the end
    trace.finish()                        # writes out.json
"""

from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager

ENABLED = False
TRACER = None

# hard cap on retained events: a pathological span-per-event loop must
# degrade to dropped spans, not to an OOM
MAX_EVENTS = 1_000_000


def _annotation_class():
    """jax.profiler.TraceAnnotation, or None where jax is absent (the
    headless tools load this module without it)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class Tracer:
    """One trace session. `path=None` collects but discards at flush
    (non-writer processes of a multi-process mesh still time their
    collectives so the collective call pattern stays uniform; an
    in-memory harness reads :attr:`events` itself)."""

    __slots__ = ("path", "events", "dropped", "tags", "_pid", "_epoch",
                 "_ann")

    def __init__(self, path: str | None):
        self.path = path
        self.events = []
        self.dropped = 0
        self.tags = {}
        self._pid = os.getpid()
        self._epoch = time.perf_counter_ns()
        self._ann = _annotation_class()

    @staticmethod
    def now() -> int:
        """Span start stamp (perf_counter_ns) for complete()."""
        return time.perf_counter_ns()

    def begin(self, name: str, **args):
        """Open span `name` -> a token for :meth:`end`. The span
        carries :attr:`tags` and `args`; it is also a profiler
        TraceAnnotation until end()."""
        if self.tags:
            args = {**self.tags, **args}
        ann = None
        if self._ann is not None:
            ann = self._ann(name, **args)
            ann.__enter__()
        return (name, time.perf_counter_ns(), args, ann)

    def end(self, tok, **args):
        """Close the span `tok` opened; `args` (counts known only at
        the end) join its args on both sinks."""
        name, t0, targs, ann = tok
        if args:
            targs = {**targs, **args}
        if ann is not None:
            if args:
                ann.set_metadata(**args)
            ann.__exit__(None, None, None)
        self.complete(name, t0, targs or None)

    def complete(self, name: str, t0_ns: int, args: dict = None,
                 tid: int = 0):
        """Record a complete span [t0_ns, now) named `name` in the
        JSON timeline only (a span from a past stamp cannot be
        annotated for the profiler; :meth:`begin` can)."""
        if len(self.events) >= MAX_EVENTS:
            self.dropped += 1
            return
        t1 = time.perf_counter_ns()
        ev = {"name": name, "ph": "X", "pid": self._pid, "tid": tid,
              "ts": (t0_ns - self._epoch) / 1000.0,
              "dur": (t1 - t0_ns) / 1000.0}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def flush(self):
        """Serialize the timeline. No-op with path=None."""
        if self.path is None:
            return
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": "shadow_tpu"}}]
        doc = {"traceEvents": meta + self.events,
               "displayTimeUnit": "ms"}
        if self.dropped:
            doc["otherData"] = {"dropped_events": self.dropped}
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.path)


def install(path: str | None) -> Tracer:
    """Enable tracing process-wide. Returns the tracer (also at
    module attribute TRACER). Idempotent-hostile by design: the caller
    that installs owns finish()."""
    global ENABLED, TRACER
    TRACER = Tracer(path)
    ENABLED = True
    return TRACER


def finish() -> Tracer | None:
    """Disable tracing and write the timeline (if a path was given).
    Returns the retired tracer so tests can inspect it."""
    global ENABLED, TRACER
    tr, TRACER, ENABLED = TRACER, None, False
    if tr is not None:
        tr.flush()
    return tr


def begin(name: str, **args):
    """Cold-path :meth:`Tracer.begin` -> a token for :func:`end`, or
    None when disabled."""
    return (TRACER, TRACER.begin(name, **args)) if ENABLED else None


def end(tok, **args):
    """Cold-path :meth:`Tracer.end` on the tracer that began `tok`; a
    None token is a no-op."""
    if tok is not None:
        tok[0].end(tok[1], **args)


@contextmanager
def span(name: str, **args):
    """Cold-path span context manager. NOT for per-chunk/per-event hot
    loops — the generator allocation is real even when disabled; hot
    paths use the explicit ``if trace.ENABLED:`` + begin()/end()
    pattern (module docstring)."""
    if not ENABLED:
        yield
        return
    tr = TRACER
    tok = tr.begin(name, **args)
    try:
        yield
    finally:
        tr.end(tok)
