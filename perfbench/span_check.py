#!/usr/bin/env python3
"""One run of a cell with the program's span recorder on.

  python3 perfbench/span_check.py --workload NAME --seed N --seconds S --trace 0|1

Runs run.py's measurement in this process with the program's tracer
(``shadow_tpu.obs.trace``) installed in memory before set-up, so the
program's spans cover set-up and the window: ``sim.build``,
``run.setup``, ``chunk`` > ``chunk.dispatch`` / ``chunk.wait``,
``digest.record`` > ``digest.pull`` / ``digest.hash``,
``jitcache.load``. With ``--trace 1`` they also sit in the profiler
slice, on the device trace's clock, and name its idle gaps.

After run.py's own lines it prints one JSON line: the span metrics
(metrics/host_gap_frac.py, metrics/record_pull_frac.py), the window's
``realtime_x`` with the recorder on (compare a run.py ``--trace 0`` run
of the same seed for the recorder's cost), and the records' pull and
hash walls and bytes. Where the program has no such spans they read
null. Exits as run.py does.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SPAN_METRICS = ("host_gap_frac", "record_pull_frac")


def reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def summary(events, w):
    """The span numbers of one run -> dict (run.py's window `w`)."""
    from perfbench.lib import spans, window
    marks = spans.named(events, "perfbench.window")
    lo = marks[0]["ts"]
    hi = lo + marks[0]["dur"]
    ctx = {"spans": events, "window_us": (lo, hi)}
    out = {name: reader(name).read(ctx) for name in SPAN_METRICS}
    pulls = [e for e in spans.named(events, "digest.pull")
             if lo <= e["ts"] < hi]
    out.update({
        "realtime_x": window.realtime_x(w["runs"], w["window_s"]),
        "window_s": w["window_s"],
        "records": len(pulls),
        "record_s": spans.total_us(events, "digest.record", lo, hi) / 1e6,
        "pull_s": spans.total_us(events, "digest.pull", lo, hi) / 1e6,
        "hash_s": spans.total_us(events, "digest.hash", lo, hi) / 1e6,
        "pull_bytes": sorted({(e.get("args") or {}).get("bytes")
                              for e in pulls} - {None}),
        "build_s": spans.total_us(events, "sim.build", lo, hi) / 1e6,
        "chunk_in_flight_s": sum(
            e - s for s, e in spans.chunks_in_flight(events, lo, hi)) / 1e6,
        "setup_load_s": spans.total_us(events, "jitcache.load", 0, lo) / 1e6,
        "spans": len(events),
    })
    return out


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from perfbench import run
    from perfbench.lib import window
    run.parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    from shadow_tpu.obs import trace as TR
    inner, held = window.run_window, {}

    def run_window(*a, **kw):
        with TR.span("perfbench.window"):
            held["w"] = inner(*a, **kw)
        return held["w"]
    window.run_window = run_window
    TR.install(None)
    try:
        rc = run.main(argv)
    finally:
        tr = TR.finish()
        window.run_window = inner
    if rc == 0:
        print(json.dumps({"span_check": summary(tr.events, held["w"])}),
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
