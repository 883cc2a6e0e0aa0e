"""Copied from the system under test (core/simtime.py) so that the plain
reference imports nothing of the program."""

from __future__ import annotations

import re

# One nanosecond is the base unit.
SIMTIME_ONE_NANOSECOND = 1
SIMTIME_ONE_MICROSECOND = 1_000
SIMTIME_ONE_MILLISECOND = 1_000_000
SIMTIME_ONE_SECOND = 1_000_000_000
SIMTIME_ONE_MINUTE = 60 * SIMTIME_ONE_SECOND
SIMTIME_ONE_HOUR = 60 * SIMTIME_ONE_MINUTE

# Sentinel for "no event" / "never": int64 max. The reference uses
# SIMTIME_INVALID/SIMTIME_MAX (shd-definitions.h:24-40).
SIMTIME_MAX = (1 << 63) - 1
SIMTIME_INVALID = SIMTIME_MAX

# Offset of simulation time 0 from the emulated Unix epoch clock
# (Jan 1 2000 00:00:00 UTC, matching shd-definitions.h:73's
# EMULATED_TIME_OFFSET so apps see a plausible wall clock).
EMULATED_TIME_OFFSET = 946_684_800 * SIMTIME_ONE_SECOND

_TIME_UNITS = {
    "ns": SIMTIME_ONE_NANOSECOND,
    "nanosecond": SIMTIME_ONE_NANOSECOND,
    "us": SIMTIME_ONE_MICROSECOND,
    "microsecond": SIMTIME_ONE_MICROSECOND,
    "ms": SIMTIME_ONE_MILLISECOND,
    "millisecond": SIMTIME_ONE_MILLISECOND,
    "s": SIMTIME_ONE_SECOND,
    "sec": SIMTIME_ONE_SECOND,
    "second": SIMTIME_ONE_SECOND,
    "m": SIMTIME_ONE_MINUTE,
    "min": SIMTIME_ONE_MINUTE,
    "minute": SIMTIME_ONE_MINUTE,
    "h": SIMTIME_ONE_HOUR,
    "hour": SIMTIME_ONE_HOUR,
}


def parse_time(value, default_unit: str = "s") -> int:
    """Parse a time value into int64 nanoseconds.

    Accepts ints/floats (interpreted in ``default_unit``, seconds by
    default — matching the reference's XML stoptime/starttime semantics)
    or strings like "10 ms", "1.5s", "250us".
    """
    if isinstance(value, (int, float)):
        return int(round(value * _TIME_UNITS[default_unit]))
    text = str(value).strip().lower()
    m = re.fullmatch(r"([0-9]*\.?[0-9]+)\s*([a-z]*)", text)
    if not m:
        raise ValueError(f"unparseable time value: {value!r}")
    num = float(m.group(1))
    unit = m.group(2) or default_unit
    # strip trailing plural
    if unit.endswith("s") and unit not in _TIME_UNITS:
        unit = unit[:-1]
    if unit not in _TIME_UNITS:
        raise ValueError(f"unknown time unit in {value!r}")
    return int(round(num * _TIME_UNITS[unit]))


def format_time(ns: int) -> str:
    """Human-readable rendering for logs: h:mm:ss.nnnnnnnnn."""
    ns = int(ns)
    if ns >= SIMTIME_MAX:
        return "never"
    secs, frac = divmod(ns, SIMTIME_ONE_SECOND)
    h, rem = divmod(secs, 3600)
    mm, ss = divmod(rem, 60)
    return f"{h:02d}:{mm:02d}:{ss:02d}.{frac:09d}"
