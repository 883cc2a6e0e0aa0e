"""Share of the window with no window-chunk program in flight: 1 - the
union of every chunk's ``chunk.dispatch`` start to its ``chunk.wait``
end, over all the window's runs, / the window (the program's spans,
lib/spans.py). Digest records, builds, each run's set-up and
finalisation, and the loop's bookkeeping all fall in the gap."""

from perfbench.lib import spans

LAYER = "run loop (engine/sim.py Simulation build and run chunk loop)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    sp, lo, hi = w
    busy = sum(e - s for s, e in spans.chunks_in_flight(sp, lo, hi))
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
