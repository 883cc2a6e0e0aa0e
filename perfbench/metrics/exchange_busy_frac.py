"""Share of the traced slice's device operation self-time labelled
``exchange``, ``exchange.sharded``, ``cap_peaks`` or ``advance``: the
work between two windows' drains (lib/passes.py)."""

from perfbench.lib import passes

LAYER = "window program (engine/window.py drain, exchange, advance)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    return passes.share(ctx, passes.EXCHANGE)
