"""Compaction's exactness against more witnesses: the 8-device mesh
and the full-tree drain, and the idle-step identity the sparse passes
rest on. The contract is tests/test_compaction.py's; these tests
compile programs of their own, so they run apart from that file's
shared dense and sparse programs.
"""

import dataclasses

import numpy as np
import jax
import pytest

from shadow_tpu.engine.sim import Simulation
from shadow_tpu.engine.state import EngineConfig
from shadow_tpu.parallel.shard import make_mesh

from test_compaction import CFG, _skewed_scen
from test_phold import phold_scenario


def test_idle_step_identity():
    """Pin the invariant compaction's exactness rests on: a not-ready
    row's step is the IDENTITY (engine.window.step_window_pass
    docstring). Dummy gather slots duplicate a not-ready host, so a
    handler that mutated state before its ready gate (e.g. an
    unconditional rng_ctr bump) would corrupt state at scale in ways
    only end-to-end equality tests could catch — this pins it at the
    unit level: stepping a host set whose every event lies past the
    window bound must leave every array bit-identical, dense and
    sparse alike."""
    import jax.numpy as jnp
    from shadow_tpu.engine.window import step_all_hosts, step_window_pass

    sim = Simulation(_skewed_scen(), engine_cfg=EngineConfig(
        num_hosts=8, active_block=3, **CFG))
    hosts, hp, sh = sim.hosts, sim.hp, sim.sh
    wend = jnp.int64(0)  # every pending start event is at >= 1s

    def assert_identity(out, label):
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(hosts)[0],
                jax.tree_util.tree_flatten_with_path(out)[0]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"{label}: leaf {jax.tree_util.keystr(path)} mutated "
                "by an all-idle step")

    assert_identity(step_all_hosts(hosts, hp, sh, wend, sim.cfg),
                    "dense")
    out, rung = step_window_pass(hosts, hp, sh, wend, sim.cfg)
    assert int(rung) == 0  # 0 ready -> smallest rung
    assert_identity(out, "sparse")


def test_compaction_sharded_matches_dense_single():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    mesh = make_mesh(8)
    single = Simulation(phold_scenario(n=16, stop=5)).run()
    scen = phold_scenario(n=16, stop=5)
    sim = Simulation(scen)
    sim.cfg = dataclasses.replace(sim.cfg, active_block=2)
    sharded = sim.run(mesh=mesh)
    assert np.array_equal(single.stats, sharded.stats)
    assert single.windows == sharded.windows


def test_hot_split_gating_bit_identical(tmp_path):
    """The hot/cold split's exactness proof: the gated drain (default
    hot_split=1, config-gated COLD_WHEN columns excluded from every
    gather/carry) produces byte-identical digest chains to the
    full-tree drain (hot_split=0, the pre-split engine) — on a no-TCP
    scenario (the 38-column `no_tcp` gate active) AND on a TCP
    scenario (socket table pinned hot, boundary columns still cold)."""
    from test_checkpoint import scen as phold_scen, CFG as PH_CFG

    def chain(name, scenario, cfg):
        path = str(tmp_path / f"{name}.jsonl")
        Simulation(scenario, engine_cfg=cfg).run(digest=path,
                                                 digest_every=8)
        return open(path, "rb").read()

    # UDP/phold tier: cpu_model off, no hosted, no tgen, no TCP —
    # every COLD_WHEN guard active, drain working set 29 columns
    base = dict(num_hosts=8, **PH_CFG)
    a = chain("ph_gated", phold_scen(), EngineConfig(**base))
    b = chain("ph_full", phold_scen(),
              EngineConfig(hot_split=0, **base))
    assert a == b, "no-TCP gated drain diverged from full-tree drain"

    # TCP tier: the skewed bulk shape (the lockstep-skew scenario the
    # compaction ladder exists for), socket table hot
    tcp = dict(num_hosts=8, **CFG)
    a = chain("tcp_gated", _skewed_scen(), EngineConfig(**tcp))
    b = chain("tcp_full", _skewed_scen(),
              EngineConfig(hot_split=0, **tcp))
    assert a == b, "TCP gated drain diverged from full-tree drain"
