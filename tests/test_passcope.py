"""Pass-time observatory tests (obs.passcope + the occupancy gate).

Decoder: the committed CI fixture (tests/data/passcope_fixture.xplane.pb,
hand-built varint records from tests/helpers/xplane_encode.py) must
decode to an EXACT pass table — every number asserted, no tolerance.
Occupancy: the lockstep waste math, recounted independently by the
pure-Python engine's pass mirror (PyEngine(count_passes=True)).
Gate: tools/perf_regress.py's occupancy column fails synthetic waste
regressions and passes flat trajectories.

Compiled-engine items (the device pass table on a live run, digest
identity with the profiler armed, the compiled-vs-python pass-mix
differential) are @slow: each adds a cold XLA compile.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from shadow_tpu.obs import metrics as MT
from shadow_tpu.obs import passcope as PC

HELPERS = Path(__file__).resolve().parent / "helpers"
sys.path.insert(0, str(HELPERS))
import xplane_encode as XE  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
PERF_REGRESS = REPO / "tools" / "perf_regress.py"

MS = 10**9  # picoseconds per millisecond


# --- decoder: the committed fixture, exactly -------------------------------

def test_fixture_file_in_sync():
    """The committed fixture IS make_fixture() — regenerating must be
    part of any encoder change (CI decodes the committed bytes)."""
    committed = Path(PC.fixture_path()).read_bytes()
    assert committed == XE.make_fixture()


def test_self_check_passes():
    assert PC.self_check() == 0


def test_fixture_decodes_to_exact_pass_table():
    scopes = PC.hlo_scope_map(PC.fixture_path())
    selfs = PC.device_self_times(PC.fixture_path())
    # the non-XLA python-thread line is ignored wholesale
    assert "python-thread" not in selfs
    dev = PC.attribute(selfs, scopes)
    assert dev["phases"]["drain"]["ms"] == 40.0
    assert dev["phases"]["exchange"]["ms"] == 30.0
    assert dev["phases"]["tcp.rx"]["ms"] == 20.0
    assert dev["phases"]["advance"]["ms"] == 5.0
    assert dev["rungs"]["w512"]["ms"] == 90.0
    assert dev["residual_ms"] == 3.0          # copy.5, unscoped HLO
    assert dev["runtime_ms"] == 2.0           # thunk glue, excluded
    assert dev["total_ms"] == 98.0
    assert dev["attributed_frac"] == round(95 / 98, 4)
    assert dev["ok"]
    assert dev["residual_top"][0] == {"op": "copy.5", "ms": 3.0}


def test_innermost_label_wins_and_rung_implies_drain(tmp_path):
    """An op under .../drain/k32/nic.tx/... is nic.tx (not drain);
    an op under a rung scope with NO handler label is drain."""
    instrs = [
        ("fusion.9", "jit(f)/jit(main)/drain/k32/nic.tx/fma"),
        ("add.1", "jit(f)/jit(main)/drain/k32/while/add"),
        ("mul.2", "jit(f)/jit(main)/cap_peaks/mul"),
    ]
    meta = XE.xplane("/host:metadata", {
        1: XE.xevent_metadata("jit_f(1)", XE.hlo_proto(instrs))}, [])
    ops = {10: XE.xevent_metadata("fusion.9"),
           11: XE.xevent_metadata("add.1"),
           12: XE.xevent_metadata("mul.2")}
    cpu = XE.xplane("/host:CPU", ops, [XE.xline(
        "tf_XLATfrtCpuClient/0",
        [(10, 0, 7 * MS), (11, 7 * MS, 2 * MS), (12, 9 * MS, MS)])])
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(XE.xspace([meta, cpu]))
    dev = PC.attribute(PC.device_self_times(str(p)),
                       PC.hlo_scope_map(str(p)))
    assert dev["phases"]["nic.tx"]["ms"] == 7.0
    assert dev["phases"]["drain"]["ms"] == 2.0
    assert dev["phases"]["cap_peaks"]["ms"] == 1.0
    assert dev["rungs"]["k32"]["ms"] == 9.0   # handler time included
    assert dev["attributed_frac"] == 1.0


def test_self_times_are_stack_based(tmp_path):
    """A parent op's time excludes its nested children — only SELF
    time lands in the table (no double counting)."""
    instrs = [("fusion.1", "jit(f)/jit(main)/drain/x"),
              ("sort.2", "jit(f)/jit(main)/exchange/x")]
    meta = XE.xplane("/host:metadata", {
        1: XE.xevent_metadata("jit_f(1)", XE.hlo_proto(instrs))}, [])
    ops = {10: XE.xevent_metadata("fusion.1"),
           11: XE.xevent_metadata("sort.2")}
    # sort.2 nested wholly inside fusion.1's span
    cpu = XE.xplane("/host:CPU", ops, [XE.xline(
        "tf_XLATfrtCpuClient/0",
        [(10, 0, 10 * MS), (11, 2 * MS, 4 * MS)])])
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(XE.xspace([meta, cpu]))
    selfs = PC.device_self_times(str(p))
    assert selfs["fusion.1"] == 6 * MS
    assert selfs["sort.2"] == 4 * MS


def test_labels_under_a_transform_are_peeled():
    """The handlers run vmapped over the drain's lanes, so jax renders
    their scopes as ``vmap(tcp.rx)`` on the op_name path: they label
    their ops as plain scopes do, and an unnamed ``vmap()`` is no
    label."""
    base = "jit(run_windows_v0)/while/body/drain/while/body/dense/"
    dev = PC.attribute(
        {"and.1": 4, "select.2": 3, "eq.3": 2, "or.4": 1},
        {"and.1": base + "vmap(tcp.rx)/jit(_where)/and",
         "select.2": base + "vmap(nic.tx)/select_n",
         "eq.3": base + "vmap()/eq",
         "or.4": "jit(f)/vmap(jit(exchange))/or"})
    assert {k: v["frac"] for k, v in dev["phases"].items()} == {
        "tcp.rx": 0.4, "nic.tx": 0.3, "drain": 0.2, "exchange": 0.1}
    assert dev["attributed_frac"] == 1.0


def test_runtime_scaffolding_excluded_from_denominator():
    selfs = {"ThunkExecutor::Execute (wait for completion)": 900 * MS,
             "fusion.1": 100 * MS}
    dev = PC.attribute(selfs, {"fusion.1": "jit(f)/jit(main)/drain/x"})
    assert dev["total_ms"] == 100.0
    assert dev["runtime_ms"] == 900.0
    assert dev["attributed_frac"] == 1.0
    assert dev["ok"]


def test_decode_dir_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        PC.decode_dir(str(tmp_path / "nope"))


# --- occupancy math --------------------------------------------------------

def test_occupancy_arithmetic_exact():
    occ = PC.occupancy({"k32": (32, 10), "dense": (64, 2)},
                       events=200, batch={"k32": 4, "dense": 1})
    # 10 sparse passes x 32 lanes x batch 4 + 2 dense x 64 x 1
    assert occ["lane_steps"] == 1408
    assert occ["passes"] == 12
    assert occ["events"] == 200
    assert occ["utilization"] == round(200 / 1408, 4)
    assert occ["waste_frac"] == round(1 - 200 / 1408, 4)
    # rung floors: k32 fires from 1 ready host; dense only past the
    # largest ladder rung
    assert occ["per_rung"]["k32"]["min_fill"] == round(1 / 32, 4)
    assert occ["per_rung"]["dense"]["min_fill"] == round(33 / 64, 4)


def test_occupancy_utilization_clamped():
    # chained NIC-TX events can exceed lane-step slots; clamp at 1.0
    occ = PC.occupancy({"dense": (4, 1)}, events=100,
                       batch={"dense": 1})
    assert occ["utilization"] == 1.0
    assert occ["waste_frac"] == 0.0


def test_shard_occupancy_skew():
    sh = PC.shard_occupancy([[10, 2], [2, 0]], [200, 40],
                            [("k32", 32), ("dense", 64)],
                            {"k32": 4, "dense": 1})
    assert len(sh["per_shard"]) == 2
    assert sh["skew"] >= 1.0
    assert all(0.0 <= w <= 1.0 for w in sh["per_shard"])


def test_top_pass():
    dev = {"phases": {"drain": {"ms": 10.0, "frac": 0.5},
                      "exchange": {"ms": 30.0, "frac": 0.3}}}
    assert PC.top_pass(dev) == ("exchange", 0.3)
    assert PC.top_pass({}) == (None, 0.0)
    assert PC.top_pass(None) == (None, 0.0)


# --- capture lifecycle (no real profiler) ----------------------------------

def test_capture_arms_after_first_chunk(monkeypatch, tmp_path):
    calls = []
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    c = PC.Capture(str(tmp_path / "tr"), max_chunks=2)
    for _ in range(5):
        c.chunk_done()
    # armed at the FIRST boundary (compile excluded), stopped after
    # its 2-chunk budget, and never re-armed
    assert [k for k, *_ in calls] == ["start", "stop"]
    assert c.chunks == 2 and c.stopped


def test_capture_degrades_when_profiler_refuses(monkeypatch, tmp_path):
    import jax

    def boom(d):
        raise RuntimeError("profiler refused")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    c = PC.Capture(str(tmp_path / "tr"))
    c.chunk_done()   # arming fails -> degrade, never raise
    out = c.result()
    assert out["available"] is False
    assert "profiler refused" in out["error"]


def test_capture_result_without_trace(tmp_path):
    c = PC.Capture(str(tmp_path / "tr"))
    out = c.result()   # never armed -> no xplane files
    assert out["available"] is False


# --- publishing + format ---------------------------------------------------

def test_publish_lands_metrics_sections():
    reg = MT.Registry()
    occ = PC.occupancy({"k32": (32, 10), "dense": (64, 2)},
                       events=200, batch={"k32": 4, "dense": 1})
    dev = PC.attribute(
        {"fusion.1": 10 * MS}, {"fusion.1": "jit(f)/jit(main)/drain/x"})
    dev["available"] = True
    PC.publish(reg, occ=occ, dev=dev,
               shards={"skew": 1.5, "per_shard": [0.1, 0.9],
                       "utilization": [0.9, 0.1]})
    snap = reg.snapshot()
    assert snap["occupancy"]["waste_frac"] == occ["waste_frac"]
    # non-digit suffixes stay flat; per-shard indices fold to a list
    assert snap["occupancy"]["rung_passes.k32"] == 10
    assert snap["occupancy"]["shard_skew"] == 1.5
    assert snap["occupancy"]["shard_waste"] == [0.1, 0.9]
    assert snap["device_phases"]["total_ms"] == 10.0
    assert snap["device_phases"]["phase_ms.drain"] == 10.0


def test_format_report_warns_below_floor():
    dev = PC.attribute(
        {"fusion.1": 10 * MS, "mystery.2": 90 * MS},
        {"fusion.1": "jit(f)/jit(main)/drain/x"})
    dev.update(available=True, chunks_traced=3)
    occ = PC.occupancy({"dense": (16, 4)}, events=20,
                       batch={"dense": 1})
    txt = PC.format_report(dev, occ)
    assert "WARNING" in txt and "mystery.2" in txt
    assert "waste_frac" in txt and "rung dense" in txt
    bad = PC.format_report({"available": False, "error": "nope"}, None)
    assert "unavailable" in bad and "nope" in bad


# --- the pyengine lockstep recount -----------------------------------------

def _recount_scen(n=8, stop=3):
    from test_phold import phold_scenario
    return phold_scenario(n=n, stop=stop)


def test_pyengine_recount_is_state_identical():
    """count_passes only reorders the drain into lockstep passes —
    hosts interact solely at the exchange, so stats must not move."""
    from shadow_tpu.engine.pyengine import PyEngine
    from shadow_tpu.engine.sim import Simulation
    plain = PyEngine(Simulation(_recount_scen())).run()
    eng = PyEngine(Simulation(_recount_scen()), count_passes=True)
    lock = eng.run()
    assert np.array_equal(plain, lock)
    assert eng.pass_mix and sum(eng.pass_mix.values()) > 0
    # 8 hosts: no ladder rung fits (4*32 > 8) -> dense-only passes
    assert set(eng.pass_mix) == {"dense"}


def test_pyengine_recount_occupancy_bounds():
    from shadow_tpu.engine.pyengine import PyEngine
    from shadow_tpu.engine.sim import Simulation
    from shadow_tpu.engine.window import pass_batches, pass_labels
    sim = Simulation(_recount_scen())
    cfg = sim.cfg
    eng = PyEngine(sim, count_passes=True)
    stats = eng.run()
    widths = dict(pass_labels(cfg, cfg.num_hosts))
    occ = PC.occupancy(
        {lbl: (widths[lbl], n) for lbl, n in eng.pass_mix.items()},
        int(stats[:, 0].sum()), pass_batches(cfg))
    assert 0.0 <= occ["waste_frac"] <= 1.0
    # a lockstep pass can never run more events than lane-steps
    assert occ["utilization"] <= 1.0


# --- the waste-aware regression gate ---------------------------------------

def _entry(waste=None, rate=1000.0, scenario="s", **kw):
    e = {"scenario": scenario, "platform": "cpu", "fingerprint": "f",
         "events_per_sec": rate, "wall_seconds": 10.0,
         "phases": {"run": 10.0}, "mem_peak_bytes": 10**9}
    if waste is not None:
        e["waste_frac"] = waste
    e.update(kw)
    return e


def _gate(tmp_path, entries, extra=()):
    p = tmp_path / "ledger.jsonl"
    with open(p, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")
    r = subprocess.run(
        [sys.executable, str(PERF_REGRESS), str(p), "--json",
         *extra], capture_output=True, text=True)
    rows = json.loads(r.stdout)["results"] if r.stdout else []
    return r.returncode, rows


def test_occupancy_gate_flat_trajectory_passes(tmp_path):
    rc, rows = _gate(tmp_path, [_entry(waste=0.30)] * 4)
    assert rc == 0
    assert rows[0]["occ_status"] == "ok"


def test_occupancy_gate_fails_waste_regression(tmp_path):
    # 0.30 history; candidate 0.60 > max(0.30*1.15, 0.35)
    rc, rows = _gate(tmp_path,
                     [_entry(waste=0.30)] * 3 + [_entry(waste=0.60)])
    assert rc == 1
    assert rows[0]["occ_status"] == "REGRESSION"
    assert rows[0]["occ_baseline"] == 0.30


def test_occupancy_gate_absolute_floor_near_zero(tmp_path):
    # near-zero medians: multiplicative band alone would flag 0.01 ->
    # 0.03; the +0.05 absolute floor keeps that noise out
    rc, rows = _gate(tmp_path,
                     [_entry(waste=0.01)] * 3 + [_entry(waste=0.03)])
    assert rc == 0
    assert rows[0]["occ_status"] == "ok"


def test_occupancy_gate_band_widens_with_history_spread(tmp_path):
    # spread [0.2,0.4] -> band capped at 0.5 -> threshold
    # max(0.3*1.5, 0.35) = 0.45: 0.44 passes, 0.46 fails
    hist = [_entry(waste=w) for w in (0.2, 0.3, 0.4)]
    rc, _ = _gate(tmp_path, hist + [_entry(waste=0.44)])
    assert rc == 0
    rc, rows = _gate(tmp_path, hist + [_entry(waste=0.46)])
    assert rc == 1
    assert rows[0]["occ_status"] == "REGRESSION"


def test_occupancy_gate_ignores_pre_passcope_history(tmp_path):
    # waste-less history neither gates nor feeds a baseline; the
    # candidate's own waste waits for a measured trajectory
    rc, rows = _gate(tmp_path,
                     [_entry()] * 3 + [_entry(waste=0.95)])
    assert rc == 0
    assert "occ_status" not in rows[0]


def test_occupancy_gate_compile_bound_exempt(tmp_path):
    # compile-bound entries carry no occupancy signal either
    hist = [_entry(waste=0.30)] * 3
    cand = _entry(waste=0.90, phases={"compile": 9.0},
                  wall_seconds=10.0)
    rc, rows = _gate(tmp_path, hist + [cand])
    assert rc == 0
    assert rows[0]["status"] == "compile-bound"


# --- compiled engine (slow: each adds a cold XLA compile) ------------------

@pytest.mark.slow
def test_passcope_run_emits_pass_table_and_digest_identical(
        tmp_path, monkeypatch):
    """One compiled phold: (a) --passcope produces a decoded pass
    table with stateflow labels or degrades cleanly; (b) the digest
    chain with the profiler armed is byte-identical to a plain run's
    (observation only); (c) occupancy rides the report and summary."""
    from shadow_tpu.engine.sim import Simulation
    monkeypatch.setenv("SHADOW_TPU_PASSCOPE_CHUNKS", "2")
    da, db = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    ra = Simulation(_recount_scen()).run(digest=str(da))
    rb = Simulation(_recount_scen()).run(
        digest=str(db), passcope=str(tmp_path / "tr"))
    assert np.array_equal(ra.stats, rb.stats)
    assert da.read_bytes() == db.read_bytes()
    assert rb.occupancy and 0.0 <= rb.occupancy["waste_frac"] <= 1.0
    assert rb.summary()["waste_frac"] == rb.occupancy["waste_frac"]
    dev = rb.device_phases
    assert dev and "available" in dev
    if dev["available"]:
        assert set(dev["phases"]) <= set(PC.PASS_LABELS)
        # the run dir carries the decoded table for trace_report
        merged = PC.load_json(str(tmp_path / "tr" / "passcope.json"))
        assert merged["device_phases"]["available"] is True
        assert merged["occupancy"]["waste_frac"] == \
            rb.occupancy["waste_frac"]


@pytest.mark.slow
def test_compiled_pass_mix_matches_pyengine_recount():
    """Skewed phold wide enough for the k32 rung: the compiled
    drain's per-rung pass counts equal the python mirror's, so the
    occupancy table is provably the drain's own accounting."""
    from shadow_tpu.engine.pyengine import PyEngine
    from shadow_tpu.engine.sim import Simulation
    from shadow_tpu.engine.window import pass_batches, pass_labels
    scen = _recount_scen(n=128, stop=2)
    rep = Simulation(scen).run()
    eng = PyEngine(Simulation(scen), count_passes=True)
    py_stats = eng.run()
    assert np.array_equal(rep.stats, py_stats)
    compiled = {lbl: r["passes"]
                for lbl, r in rep.occupancy["per_rung"].items()
                if r["passes"]}
    assert compiled == eng.pass_mix
    cfg = eng.cfg
    widths = dict(pass_labels(cfg, cfg.num_hosts))
    occ = PC.occupancy(
        {lbl: (widths[lbl], n) for lbl, n in eng.pass_mix.items()},
        int(py_stats[:, 0].sum()), pass_batches(cfg))
    assert occ["waste_frac"] == rep.occupancy["waste_frac"]
