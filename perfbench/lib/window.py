"""The measured window: whole runs of the deployment, back to back.

The window starts the first run at once and starts another while the
clock reads less than `seconds` since the window opened; the run in
progress when it passes finishes and counts. So the window is all the
work of the runs over all the time they took, and holds at least one
whole run.
"""

from __future__ import annotations

import time


def run_window(build, run, seconds, clock=time.perf_counter,
               annotate=None):
    """-> {"runs": [result], "window_s", "build_s", "run_s"}.

    `build()` makes a fresh run object, `run(obj)` runs it to its stop
    and returns its result dict; both are timed on `clock`.
    `annotate(name)` (optional) returns a context manager wrapped
    around each call, for the profiler's host timeline."""
    if seconds <= 0:
        raise ValueError("the window needs a positive length")
    ann = annotate or (lambda name: _Null())
    runs, build_s, run_s = [], 0.0, 0.0
    t0 = clock()
    while True:
        b0 = clock()
        with ann("bench.build"):
            obj = build()
        b1 = clock()
        with ann("bench.run"):
            res = run(obj)
        del obj
        r1 = clock()
        build_s += b1 - b0
        run_s += r1 - b1
        runs.append(res)
        if r1 - t0 >= seconds:
            break
    return {"runs": runs, "window_s": clock() - t0, "build_s": build_s,
            "run_s": run_s}


def realtime_x(runs, window_s) -> float:
    """Simulated seconds of all the runs over the window's wall time."""
    return sum(r["sim_ns"] for r in runs) / 1e9 / window_s


def events_per_s(runs, window_s) -> float:
    return sum(r["events"] for r in runs) / window_s


class ChunkClock:
    """Host wall of the window program's chunks and of the digest
    recorder's records (one after each chunk, one at the end of a run).
    A chunk runs from a run's start, or the end of its previous record,
    to the start of the next record; `windows` counts the windows the
    chunks simulated. The device runs a chunk as one program and the
    run loop waits for it, so a chunk's wall is its device time plus its
    dispatch. `on_first` (optional) is called once, at the end of the
    first record, as the next chunk starts: mid-run, away from a
    record."""

    def __init__(self, clock=time.perf_counter, on_first=None):
        self.clock = clock
        self.on_first = on_first
        self.wall = 0.0
        self.record_wall = 0.0
        self.windows = 0
        self._t, self._w, self._t_record = None, 0, None

    def start_run(self):
        self._t, self._w = self.clock(), 0

    def __call__(self, edge, window):
        now = self.clock()
        if edge == "start":
            self.wall += now - self._t
            self.windows += window - self._w
            self._t_record = now
        else:
            self.record_wall += now - self._t_record
            if self.on_first is not None:
                first, self.on_first = self.on_first, None
                first()
                now = self.clock()
            self._t, self._w = now, window


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
