"""The plain reference for the network-simulation configurations: the
deployment description -> per-host statistics, event for event.

A heap-and-dict engine (engine.py) that executes each host's events in
(time, sequence) order, window by window, with the simulator's
exchange and deferral rules; tables from world.py. It imports nothing
of the system under test and takes nothing the program builds, but it
is not an independent implementation: engine.py and the modules beside
it are a frozen copy of the program's own sequential engine
(engine/pyengine.py) and of the TCP, cubic, SACK, app, routing and
attachment code it calls, as they stood when this benchmark was
written. The comparison is therefore a consistency check of the
vectorised timed path against its sequential twin: it catches any
later departure from these semantics, and cannot catch a fault that
both had when the copy was made.

``run(dep, control=True)`` is the control: the same engine with one
stated guarantee broken, exact delivery — every CONTROL_EVERY-th
packet leaving an outbox is lost as if to a full queue. The comparison
in lib/compare.py must call it not correct.
"""

from __future__ import annotations

from . import defs
from .engine import PyEngine
from .world import build

CONTROL_EVERY = 500

STAT_NAMES = sorted((n for n in dir(defs) if n.startswith("ST_")),
                    key=lambda n: getattr(defs, n))


class _Control(PyEngine):
    def __init__(self, world):
        super().__init__(world)
        self._sent = 0

    def _exchange(self):
        for host in self.hosts:
            keep = []
            for item in host.outbox:
                self._sent += 1
                if self._sent % CONTROL_EVERY == 0:
                    self.stats[host.hid, defs.ST_PKTS_DROP_Q] += 1
                else:
                    keep.append(item)
            host.outbox = keep
        return super()._exchange()


def run(dep: dict, control: bool = False):
    """-> (stats [H, N_STATS] int64, the stat names in column order)."""
    world = build(dep)
    eng = (_Control if control else PyEngine)(world)
    return eng.run(), list(STAT_NAMES)
