"""The system under test, driven the way its CLI drives it.

``shadow_tpu/__main__.py`` builds the auto engine config, overrides it
with ``--engine-caps``, builds a ``Simulation`` and sets the runahead
on its shared scalars; ``Simulation.run`` then steps the window-chunk
program to the stop time. One :func:`run_once` is one such run, from
a fresh ``Simulation`` (the chunk program donates the host state, so a
Simulation runs once), with the digest chain recorded at the CLI's
default cadence.

Nothing here is measured or compared: lib/window.py times the calls,
lib/compare.py judges the results.
"""

from __future__ import annotations

import dataclasses

CAP_NAMES = {"chunk": "chunk_windows"}


def scenario_of(dep: dict):
    """The plain deployment -> the program's Scenario."""
    from shadow_tpu.core.config import HostSpec, ProcessSpec, Scenario
    return Scenario(
        stop_time=dep["stop_ns"], topology_graphml=dep["topology_graphml"],
        seed=dep["seed"],
        hosts=[HostSpec(id=h["id"], quantity=h["quantity"],
                        geocode_hint=h.get("geocode"), processes=[
            ProcessSpec(plugin=p["plugin"], start_time=p["start_ns"],
                        arguments=p["args"]) for p in h["processes"]])
            for h in dep["hosts"]])


class Deployment:
    """Set-up work shared by every run of one deployment: the topology
    (all-pairs routes) and the engine config, as the CLI computes
    them."""

    def __init__(self, dep: dict):
        from shadow_tpu.engine.sim import auto_engine_config
        from shadow_tpu.routing.topology import build_topology
        self.dep = dep
        scen = scenario_of(dep)
        self.topo = build_topology(dep["topology_graphml"])
        cfg = auto_engine_config(scen, self.topo)
        caps = {CAP_NAMES.get(k, k): int(v) for k, v in dep["caps"].items()}
        self.cfg = dataclasses.replace(cfg, **caps)

    def build(self, stop_ns: int = None):
        """A fresh Simulation, runahead applied (the CLI's --runahead)."""
        import jax.numpy as jnp
        from shadow_tpu.engine.sim import Simulation
        scen = scenario_of(self.dep)
        if stop_ns is not None:
            scen.stop_time = int(stop_ns)
        sim = Simulation(scen, topology=self.topo, engine_cfg=self.cfg)
        sim.sh = sim.sh.replace(
            min_jump=jnp.int64(max(self.dep["runahead_ns"], 1)))
        return sim


def run_once(sim, on_record=None) -> dict:
    """Run `sim` to its stop time with the digest chain on -> the
    results every comparison and metric reads. `on_record(edge,
    window)`, if given, is called as the run loop hands the recorder
    each record (after each chunk, and at the end), with edge "start"
    before the state is hashed and "end" after."""
    from shadow_tpu.engine import defs
    from shadow_tpu.obs import digest as DG
    # in memory, no per-host detail: the chain hash is what repeats
    # are compared on
    rec = DG.install(None, every=DG.DEFAULT_EVERY, host_detail=0)
    if on_record is not None:
        record = rec.record

        def hooked(hosts, H, window, *args, **kw):
            on_record("start", int(window))
            try:
                return record(hosts, H, window, *args, **kw)
            finally:
                on_record("end", int(window))
        rec.record = hooked
    try:
        report = sim.run()
    finally:
        rec = DG.finish()
    cost = report.cost_model()
    return {
        "stats": report.stats,
        "events": int(report.stats[:, defs.ST_EVENTS].sum()),
        "sim_ns": int(report.sim_time_ns),
        "windows": int(report.windows),
        "digest": rec.chain_hex,
        "digest_records": len(rec.records),
        "capacity": report.capacity_report(),
        "passes_per_window": cost.get("passes_per_window"),
        "waste_frac": (report.occupancy or {}).get("waste_frac"),
    }


def overflow(result: dict) -> int:
    """Packets or pushes lost to a capacity (deferrals are exact and
    not counted)."""
    return sum(int(r["overflow"]) for r in result["capacity"])
