"""Active-set compaction (EngineConfig.active_block) — the TPU-native
analogue of the reference's host-steal load balancing
(/root/reference/src/main/core/scheduler/shd-scheduler-policy-host-steal.c:
163-191): a lockstep pass steps only the ready hosts instead of paying
a full all-hosts pass per busiest-host event.

The contract under test: compaction changes WHICH rows a pass touches,
never the per-host (time, seq) execution order — so every run must be
bit-identical to the dense engine, including under sharding and in the
differential harness (the mesh, the full-tree drain and the idle-step
identity are in tests/test_compaction_invariants.py, so the two files'
compiles run on two workers).
"""

import numpy as np

from shadow_tpu.core.config import HostSpec, ProcessSpec, Scenario
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.engine.state import EngineConfig
from shadow_tpu.engine.pyengine import PyEngine

from test_tcp import poi_topology


def _skewed_scen(stop=40):
    """One busy server, many mostly-idle clients — the lockstep-skew
    shape compaction exists for."""
    return Scenario(
        stop_time=stop * 10**9,
        topology_graphml=poi_topology(),
        hosts=[
            HostSpec(id="server", processes=[
                ProcessSpec(plugin="bulkserver", start_time=10**9,
                            arguments="port=80")]),
            HostSpec(id="client", quantity=7, processes=[
                ProcessSpec(plugin="bulk", start_time=2 * 10**9,
                            arguments="peer=server port=80 size=150000 "
                                      "count=2 pause=3s")]),
        ],
    )


CFG = dict(qcap=32, scap=12, obcap=16, incap=24, txqcap=12,
           chunk_windows=8)


def _run(scen, block, mesh=None):
    sim = Simulation(scen, engine_cfg=EngineConfig(
        num_hosts=8, active_block=block, **CFG))
    return sim.run(mesh=mesh)


def test_compaction_bit_identical_dense_vs_sparse():
    dense = _run(_skewed_scen(), 0)
    sparse = _run(_skewed_scen(), 3)      # < busy-host count: exercises
    # both the K-cap (more ready than block) and the dummy-slot path
    assert np.array_equal(dense.stats, sparse.stats)
    assert dense.windows == sparse.windows


def test_compaction_block_exceeds_hosts():
    """block >= H degenerates gracefully (K clamped to H)."""
    dense = _run(_skewed_scen(stop=20), 0)
    sparse = _run(_skewed_scen(stop=20), 64)
    assert np.array_equal(dense.stats, sparse.stats)


def test_compaction_differential():
    """The differential harness holds with compaction on: the compiled
    engine with active-set gathering still matches the heap engine bit
    for bit."""
    from test_differential import TCP_COMPARE

    # the sparse program of the dense-vs-sparse test, compiled once
    cfg = EngineConfig(num_hosts=8, active_block=3, **CFG)
    jax_stats = Simulation(_skewed_scen(), engine_cfg=cfg).run().stats
    py_stats = PyEngine(Simulation(_skewed_scen(), engine_cfg=cfg)).run()
    for st in TCP_COMPARE:
        assert np.array_equal(jax_stats[:, st], py_stats[:, st]), st


def test_event_batch_bit_identical():
    """Draining up to B consecutive due events per gathered host in one
    sparse pass (EngineConfig.event_batch) is a pass-schedule change
    only — per-host (time, seq) order is preserved — so stats must be
    bit-identical to the one-event-per-pass engine."""
    sim1 = Simulation(_skewed_scen(), engine_cfg=EngineConfig(
        num_hosts=8, active_block=3, event_batch=1, **CFG))
    # B: the default, the sparse program the tests above compiled
    simB = Simulation(_skewed_scen(), engine_cfg=EngineConfig(
        num_hosts=8, active_block=3, **CFG))
    r1, rB = sim1.run(), simB.run()
    assert np.array_equal(r1.stats, rB.stats)
    assert r1.windows == rB.windows
    # batching may only LOWER the pass count
    assert (rB.cost_model()["passes_total"] <=
            r1.cost_model()["passes_total"])


def test_exchange_sort_compaction_bit_identical():
    """The exchange's sort compaction (EngineConfig.exsortcap) is a
    sort-input change only: a stable sort of the compacted survivor
    list equals the full stable sort filtered to survivors, so stats
    must match bit for bit. A tiny cap forces BOTH branches over the
    run (small windows compact, burst windows fall back)."""
    full = _run(_skewed_scen(), 0)
    sim = Simulation(_skewed_scen(), engine_cfg=EngineConfig(
        num_hosts=8, active_block=0, exsortcap=16, **CFG))
    compact = sim.run()
    assert np.array_equal(full.stats, compact.stats)
    assert full.windows == compact.windows
    # tiny dstcap exercises BOTH dest-merge branches too (windows with
    # <= 2 receiving hosts merge compacted, busier ones fall back)
    sim2 = Simulation(_skewed_scen(), engine_cfg=EngineConfig(
        num_hosts=8, active_block=0, exsortcap=16, dstcap=2, **CFG))
    compact2 = sim2.run()
    assert np.array_equal(full.stats, compact2.stats)


def test_hot_fields_gating_per_config():
    """hot_fields(cfg) activates exactly the declared COLD_WHEN gates
    for a config, and hot_split=0 restores the full pytree."""
    import dataclasses as dc

    from shadow_tpu.engine.state import (COLD_FIELDS, HOT_FIELDS,
                                         Hosts, hot_fields)

    # phold-style: no TCP, no hosted, no tgen, single process
    udp = EngineConfig(num_hosts=4, app_kinds=(0, 3), uses_tcp=False)
    hot = hot_fields(udp)
    assert "sk_sack_s" not in hot and "sk_cwnd" not in hot
    assert "sk_proc" not in hot          # single-process gate
    assert "cpu_avail" not in hot and "hw_cnt" not in hot
    assert "tgen_sync" not in hot
    # UDP-touched socket columns stay hot
    for f in ("sk_used", "sk_proto", "sk_lport", "sk_snd_end",
              "sk_rcv_nxt", "sk_timer_gen"):
        assert f in hot, f
    assert len(hot) == 29

    # multi-process UDP: wake routing reads sk_proc — pinned hot
    assert "sk_proc" in hot_fields(dc.replace(udp, procs_per_host=2))

    # TCP tier (tgen absent): socket table hot, boundary gates active
    tcp = EngineConfig(num_hosts=4, app_kinds=(0, 9, 10),
                       uses_tcp=True)
    hot = hot_fields(tcp)
    assert "sk_sack_s" in hot and "sk_cwnd" in hot
    assert "cpu_avail" not in hot and "tgen_sync" not in hot

    # hosted / cpu-model / unknown app set pin their columns hot
    assert "hw_cnt" in hot_fields(dc.replace(udp, hostedcap=32))
    assert "cpu_avail" in hot_fields(dc.replace(udp, cpu_model=True))
    assert "tgen_sync" in hot_fields(EngineConfig(num_hosts=4))

    # the escape hatch carries everything, static cold included
    allf = hot_fields(EngineConfig(num_hosts=4, hot_split=0))
    assert set(allf) == set(Hosts.__dataclass_fields__)
    assert set(HOT_FIELDS) | COLD_FIELDS == set(allf)
