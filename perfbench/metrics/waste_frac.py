"""Share of the lane-steps of the compaction ladder's passes that ran
no event (SimReport.occupancy), an exact count."""

LAYER = "compaction ladder (engine/window.py ladder_of)"
MOVES = "realtime_x"
UNIT = "%"
SOURCE = "program_counter"


def read(ctx):
    v = ctx["runs"][0].get("waste_frac")
    return 100.0 * float(v) if v is not None else None
