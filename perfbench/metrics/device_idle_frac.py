"""Share of the traced stretch in which no operation ran on the
device: 1 - (union of operation intervals) / stretch, averaged over
the devices used."""

LAYER = "device (one TPU v5e)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    span = ctx["trace_hi"] - ctx["trace_lo"]
    if span <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_ns"] / span)
