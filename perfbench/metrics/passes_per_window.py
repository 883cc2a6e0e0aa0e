"""Lockstep passes the drain ran per simulated window, from the
program's own pass counters (SimReport.cost_model), an exact count."""

LAYER = "window program (engine/window.py drain, exchange, advance)"
MOVES = "realtime_x"
UNIT = "passes"
SOURCE = "program_counter"


def read(ctx):
    v = ctx["runs"][0].get("passes_per_window")
    return float(v) if v else None
