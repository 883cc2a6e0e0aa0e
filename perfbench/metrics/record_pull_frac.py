"""Share of the window spent pulling the state from the device for the
digest recorder's records: the ``digest.pull`` spans (the program's
spans, lib/spans.py) over the window. The rest of a record is
``digest.hash``."""

from perfbench.lib import spans

LAYER = "digest recorder (obs/digest.py determinism chain)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    w = spans.window(ctx)
    if w is None or not spans.named(w[0], "digest.pull"):
        return None
    sp, lo, hi = w
    return 100.0 * spans.total_us(sp, "digest.pull", lo, hi) / (hi - lo)
