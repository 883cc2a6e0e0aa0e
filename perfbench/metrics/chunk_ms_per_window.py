"""Host wall of the window program's chunks per simulated window, over
every run in the window (lib/window.ChunkClock: from a run's start or
its previous digest record to the next record). The run loop waits for
each chunk, which the device runs as one program, so this is the
window program's device time per window plus its dispatch."""

LAYER = "window program (engine/window.py drain, exchange, advance)"
MOVES = "realtime_x"
UNIT = "ms"
SOURCE = "host_clock"


def read(ctx):
    if not ctx.get("chunk_windows"):
        return None
    return 1000.0 * ctx["chunk_s"] / ctx["chunk_windows"]
