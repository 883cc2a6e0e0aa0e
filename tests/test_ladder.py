"""The per-pass compaction ladder (engine.window.ladder_of) and the
batch each rung drains (engine.window.rung_batch, pass_batches).

The ladder table is pure Python. The one-event rung's bit-identity runs
the UDP tier at 8 hosts, with the rung candidates patched so that a
one-event top rung fits there: it drains exactly what a dense pass
drains, and runs the wider passes in chunks of its width, so stats,
windows and the pass count stay those of the ladder without it.
"""

import dataclasses

import numpy as np
import pytest

from shadow_tpu.engine import window as W
from shadow_tpu.engine.state import EngineConfig
from shadow_tpu.obs import passcope as PC

B = EngineConfig(num_hosts=8).event_batch

# H -> the AUTO ladder; outside [1,024, 2,048) the ladder of the
# two-candidate rule (32, 512) it replaced
LADDERS = {
    8: [], 127: [], 128: [32], 1023: [32], 1024: [32, 256],
    2000: [32, 256], 2047: [32, 256], 2048: [32, 512], 8192: [32, 512],
    10000: [32, 512],
}


@pytest.mark.parametrize("H", sorted(LADDERS))
def test_ladder_table(H):
    cfg = EngineConfig(num_hosts=H)
    ks = W.ladder_of(cfg, H)
    assert ks == LADDERS[H]
    if not 1024 <= H < 2048:
        assert ks == [k for k in (32, 512) if 4 * k <= H]
    batches = [W.rung_batch(cfg, k) for k in ks]
    assert batches == [1 if k == 256 else B for k in ks]
    labels = W.pass_labels(cfg, H)
    assert [lbl for lbl, _ in labels] == (
        [f"w{k}" for k in W.window_ladder(cfg, H)] +
        [f"k{k}" for k in ks] + ["dense"])
    table = W.pass_batches(cfg, H)
    assert set(table) == {lbl for lbl, _ in labels}
    assert table["dense"] == 1
    assert [table[f"k{k}"] for k in ks] == batches


def test_explicit_rung_keeps_its_batch():
    """Only AUTO's 256 rung drains one event: an explicit
    active_block of 256 keeps the sparse batch."""
    cfg = EngineConfig(num_hosts=2000, active_block=256)
    assert W.ladder_of(cfg) == [256]
    assert W.rung_batch(cfg, 256) == B
    assert W.ladder_of(EngineConfig(num_hosts=2000, active_block=0)) == []


def test_occupancy_charges_a_one_event_rung_its_width():
    cfg = EngineConfig(num_hosts=2000)
    mix = {"k32": (32, 234), "k256": (256, 1824), "dense": (2000, 622)}
    occ = PC.occupancy(mix, 608229, W.pass_batches(cfg))
    rung = occ["per_rung"]["k256"]
    assert rung["batch"] == 1 and rung["passes"] == 1824
    assert rung["lane_steps"] == 1824 * 256
    assert occ["per_rung"]["k32"]["lane_steps"] == 234 * 32 * B
    assert occ["lane_steps"] == 234 * 32 * B + 1824 * 256 + 622 * 2000


# --- the one-event rung, compiled ------------------------------------------

def _scen():
    from test_phold import phold_scenario
    return phold_scenario(n=8, stop=3)


@pytest.fixture
def fresh_programs():
    """Drop the chunk programs a test compiles under a patched ladder,
    so no later test in this process reuses them."""
    before = set(W._RW_INSTANCES)
    yield
    for key in set(W._RW_INSTANCES) - before:
        del W._RW_INSTANCES[key]


def _sim(**cfg):
    from shadow_tpu.engine.sim import Simulation
    sim = Simulation(_scen())
    sim.cfg = dataclasses.replace(sim.cfg, **cfg)
    return sim


def _run(monkeypatch, rungs, one_event, **cfg):
    """Run under the rung candidates `rungs`, of which `one_event`
    drains one event per host; AUTO programs compiled under another
    patch are dropped first, since their key is the same config."""
    for key in set(W._RW_INSTANCES):
        if key[0].active_block == -1:
            del W._RW_INSTANCES[key]
    monkeypatch.setattr(W, "_AUTO_RUNGS", rungs)
    monkeypatch.setattr(W, "_ONE_EVENT_RUNG", one_event)
    sim = _sim(**cfg)
    return sim.cfg, sim.run()


def test_one_event_rung_keeps_the_dense_schedule(monkeypatch,
                                                 fresh_programs):
    from shadow_tpu.engine.pyengine import PyEngine
    # 8 hosts: candidates (1, 2, 4) give the ladder [1, 2] under the
    # quarter rule, and the 2 rung drains one event per host
    cfg, mid = _run(monkeypatch, (1, 2, 4), 2)
    assert W.ladder_of(cfg) == [1, 2]
    assert W.pass_batches(cfg) == {"k1": B, "k2": 1, "dense": 1}
    per_rung = mid.occupancy["per_rung"]
    assert per_rung["k2"]["passes"] > 0 and per_rung["k2"]["batch"] == 1
    assert per_rung["k1"]["passes"] > 0 and per_rung["dense"]["passes"] > 0
    # the sequential mirror, under the same patch, rung by rung
    eng = PyEngine(_sim(), count_passes=True)
    assert np.array_equal(mid.stats, eng.run())
    assert eng.pass_mix == {lbl: r["passes"]
                            for lbl, r in per_rung.items() if r["passes"]}

    _, without = _run(monkeypatch, (1,), 2)
    _, dense = _run(monkeypatch, (1, 2, 4), 2, active_block=0)
    assert np.array_equal(mid.stats, dense.stats)
    assert np.array_equal(mid.stats, without.stats)
    assert mid.windows == dense.windows == without.windows
    assert "k2" not in without.occupancy["per_rung"]
    assert (mid.cost_model()["passes_total"] ==
            without.cost_model()["passes_total"])
