"""Share of the traced slice's device operation self-time labelled by
an event handler: ``tcp.rx``, ``tcp.timer``, ``udp.deliver``,
``nic.tx`` or ``nic.rx_admit`` (lib/passes.py)."""

from perfbench.lib import passes

LAYER = "window program (engine/window.py drain, exchange, advance)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    return passes.share(ctx, passes.HANDLERS)
