"""Share of the window spent building each run's Simulation (topology
attachment, app compile, initial state) on the host clock."""

LAYER = "run loop (engine/sim.py Simulation build and run chunk loop)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "host_clock"


def read(ctx):
    if not ctx["window_s"]:
        return None
    return 100.0 * ctx["build_s"] / ctx["window_s"]
