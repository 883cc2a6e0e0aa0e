"""Share of the window spent in the digest recorder's records (the
state pulled from the device and hashed after each chunk and at the end
of each run; lib/window.ChunkClock), on the host clock. The run loop
waits for each record, so this time adds to every run's wall."""

LAYER = "digest recorder (obs/digest.py determinism chain)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    if not ctx["window_s"] or "record_s" not in ctx:
        return None
    return 100.0 * ctx["record_s"] / ctx["window_s"]
