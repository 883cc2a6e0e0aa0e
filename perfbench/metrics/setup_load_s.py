"""Seconds the program spent loading its executables from the
executable cache (``--aot-cache``), the sum of its ``jitcache.load``
walls (shadow_tpu/serving/aotcache.py STATS). The window program is
loaded in set-up and held in memory after, so this is set-up's load.
Reported only for a set-up served wholly from the cache: where any
executable was compiled instead (a checkout's first run), the load
wall measures part of set-up or none of it, and the reader gives
None."""

LAYER = "entry (set-up: executable cache load)"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "host_clock"


def read(ctx):
    try:
        from shadow_tpu.serving.aotcache import STATS
    except ImportError:
        return None
    if STATS["compiles"]:
        return None
    return float(STATS["load_wall_s"])
