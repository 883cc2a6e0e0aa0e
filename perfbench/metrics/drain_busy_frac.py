"""Share of the traced slice's device operation self-time labelled
``drain`` or a compaction rung: the drain's own lockstep work, its
event handlers left out (lib/passes.py)."""

from perfbench.lib import passes

LAYER = "compaction ladder (engine/window.py ladder_of)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    return passes.share(ctx, passes.DRAIN)
