"""The program's own host spans (shadow_tpu/obs/trace.py, Chrome
trace-event dicts with microsecond ``ts``/``dur``) over the measured
window.

A reader finds them in ``ctx["spans"]`` with the window's bounds on the
same clock in ``ctx["window_us"]``; run.py supplies neither, so the
readers that need them give nothing there (span_check.py does).
"""

from __future__ import annotations

from perfbench.lib.trace import clip, union


def window(ctx):
    """-> (spans, lo_us, hi_us), or None when the context has none."""
    spans, win = ctx.get("spans"), ctx.get("window_us")
    if not spans or not win or win[1] <= win[0]:
        return None
    return spans, win[0], win[1]


def named(spans, name):
    return [e for e in spans if e.get("name") == name and "dur" in e]


def total_us(spans, name, lo, hi) -> float:
    """Time under spans `name` inside [lo, hi) (they do not overlap)."""
    return sum(e - s for s, e in clip(
        [(x["ts"], x["ts"] + x["dur"]) for x in named(spans, name)],
        lo, hi))


def chunks_in_flight(spans, lo, hi):
    """Union of each chunk's ``chunk.dispatch`` start to its
    ``chunk.wait`` end inside [lo, hi): the time a window-chunk program
    was dispatched and not yet waited out."""
    start = {}
    for x in named(spans, "chunk.dispatch"):
        a = x.get("args") or {}
        start[(a.get("run"), a.get("chunk"))] = x["ts"]
    iv = []
    for x in named(spans, "chunk.wait"):
        a = x.get("args") or {}
        s = start.get((a.get("run"), a.get("chunk")))
        if s is not None:
            iv.append((s, x["ts"] + x["dur"]))
    return union(clip(iv, lo, hi))
