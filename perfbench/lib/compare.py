"""What decides ``correct``: the timed path's results against the plain
reference's, and the guarantees the configuration states.

Every number compared has its limit; a run is correct when each is
within it. The limits are exact (0): the simulator is deterministic
and the reference, a frozen sequential twin of the program
(reference/netref.py says what that can and cannot catch), implements
the same semantics event for event, so any difference is a fault
(PERF.md gives the readings they rest on).

- ``stat_cells_off``: per-host statistic cells (events, packets sent
  and received, bytes delivered, retransmits, transfers completed,
  drops, deferrals, ...) in which the window's first run differs from
  the reference;
- ``events_off``: the event count's difference;
- ``capacity_drops``: packets or pushes the window's runs lost to a
  capacity (the exact-delivery guarantee; deferrals are exact);
- ``repeats_off``: runs in the window whose statistics or digest chain
  differ from the window's first run (the determinism guarantee).
"""

from __future__ import annotations

import numpy as np

LIMITS = {"stat_cells_off": 0, "events_off": 0, "capacity_drops": 0,
          "repeats_off": 0}


def align(prog_stats, prog_names, ref_stats, ref_names):
    """Reorder the reference's columns to the program's, by name."""
    if sorted(prog_names) != sorted(ref_names):
        raise ValueError(f"stat tables differ: program {prog_names} vs "
                         f"reference {ref_names}")
    idx = [ref_names.index(n) for n in prog_names]
    return np.asarray(prog_stats), np.asarray(ref_stats)[:, idx]


def numbers(runs, prog_names, ref_stats, ref_names, overflow):
    """-> {name: value} for the limits above. `runs`: the window's run
    results (lib/system.run_once), first run first."""
    first = runs[0]
    p, r = align(first["stats"], prog_names, ref_stats, ref_names)
    ev = prog_names.index("ST_EVENTS")
    if p.shape != r.shape:
        cells = int(max(p.size, r.size))
    else:
        cells = int((p != r).sum())
    repeats = sum(1 for x in runs[1:]
                  if x["digest"] != first["digest"]
                  or not np.array_equal(x["stats"], first["stats"]))
    return {
        "stat_cells_off": cells,
        "events_off": int(abs(int(p[:, ev].sum()) - int(r[:, ev].sum()))),
        "capacity_drops": int(sum(overflow(x) for x in runs)),
        "repeats_off": int(repeats),
    }


def judge(nums: dict) -> bool:
    return all(nums[k] <= LIMITS[k] for k in LIMITS)


def lines(nums: dict) -> list:
    return [f"{k} {nums[k]} limit {LIMITS[k]}" for k in LIMITS]
