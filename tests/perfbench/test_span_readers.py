"""The per-layer readers of the program's spans and pass labels, on
synthetic contexts: each gives its value where its input is, and None
where it is not."""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import span_check  # noqa: E402
from perfbench.lib import spec as specmod  # noqa: E402
from perfbench.lib.trace import Trace  # noqa: E402


def _reader(name):
    return span_check.reader(name)


def _listed(name):
    by_name = {m["name"]: m for m in specmod.Spec(ROOT).data["per_layer"]}
    return specmod.reader(by_name[name])


def _op(name, start_ns, dur_ns):
    return SimpleNamespace(name=name, start_ns=start_ns, duration_ns=dur_ns,
                           end_ns=start_ns + dur_ns)


def _span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


# one run of two chunks and a record, in a window [0, 1000) us
SPANS = [
    _span("perfbench.window", 0, 1000),
    _span("sim.build", 0, 50),
    _span("chunk", 100, 300, run=2, chunk=0),
    _span("chunk.dispatch", 110, 20, run=2, chunk=0),
    _span("chunk.wait", 130, 250, run=2, chunk=0),
    _span("digest.record", 400, 120, run=2, chunk=0),
    _span("digest.pull", 405, 30, run=2, chunk=0, bytes=4096),
    _span("digest.hash", 440, 75, run=2, chunk=0),
    _span("chunk", 550, 400, run=2, chunk=1),
    _span("chunk.dispatch", 560, 40, run=2, chunk=1),
    _span("chunk.wait", 600, 340, run=2, chunk=1),
    _span("jitcache.load", -500, 200),
]


def test_host_gap_frac():
    r = _reader("host_gap_frac")
    ctx = {"spans": SPANS, "window_us": (0, 1000)}
    # in flight [110, 380) and [560, 940): 650 of 1000 us
    assert r.read(ctx) == pytest.approx(35.0)
    # clipped to the window
    assert r.read(dict(ctx, window_us=(0, 500))) == pytest.approx(46.0)
    assert r.read({"window_us": (0, 1000)}) is None
    assert r.read({"spans": SPANS}) is None
    assert r.read({"spans": [SPANS[1]], "window_us": (0, 1000)}) is None


def test_record_pull_frac():
    r = _reader("record_pull_frac")
    assert r.read({"spans": SPANS, "window_us": (0, 1000)}) == \
        pytest.approx(3.0)
    no_pull = [s for s in SPANS if s["name"] != "digest.pull"]
    assert r.read({"spans": no_pull, "window_us": (0, 1000)}) is None
    assert r.read({}) is None


def test_span_check_summary():
    w = {"runs": [{"sim_ns": 2 * 10**9}], "window_s": 0.001}
    out = span_check.summary(SPANS, w)
    assert out["host_gap_frac"] == pytest.approx(35.0)
    assert out["record_pull_frac"] == pytest.approx(3.0)
    assert out["realtime_x"] == pytest.approx(2000.0)
    assert out["records"] == 1 and out["pull_bytes"] == [4096]
    assert out["pull_s"] == pytest.approx(30e-6)
    assert out["hash_s"] == pytest.approx(75e-6)
    assert out["chunk_in_flight_s"] == pytest.approx(650e-6)
    assert out["build_s"] == pytest.approx(50e-6)


def test_setup_load_s(monkeypatch):
    from shadow_tpu.serving import aotcache
    monkeypatch.setitem(aotcache.STATS, "load_wall_s", 12.5)
    monkeypatch.setitem(aotcache.STATS, "compiles", 0)
    r = _listed("setup_load_s")
    assert r.read({}) == 12.5
    # a set-up that compiled, wholly or in part, measured no load
    monkeypatch.setitem(aotcache.STATS, "compiles", 1)
    assert r.read({}) is None
    # a program without the executable cache
    monkeypatch.setitem(sys.modules, "shadow_tpu.serving.aotcache", None)
    assert r.read({}) is None


def _trace_ctx(scopes, modules=("jit_run_windows_v0(42)",)):
    us = 1000
    ops = [_op("fusion.1", 0, 6_000 * us), _op("fusion.2", 1_000 * us,
                                                 2_000 * us),
           _op("sort.3", 6_000 * us, 2_000 * us),
           _op("copy.4", 8_000 * us, 1_000 * us),
           _op("reduce.5", 9_000 * us, 500 * us),
           _op("add.6", 9_500 * us, 500 * us),
           _op("late.7", 20_000 * us, 1_000 * us)]
    mods = [_op(m, 0, 10_000 * us) for m in modules]
    tr = Trace([("/device:TPU:0", [("XLA Ops", ops),
                                   ("XLA Modules", mods)])])
    return {"trace": tr, "trace_lo": 0, "trace_hi": 10_000 * us,
            "scopes": scopes}


SCOPES = {
    # the while loop's own time, less the fusion nested in it
    "fusion.1": "jit(run_windows_v0)/while/body/drain/while/body/w32/gather",
    # the handlers run vmapped: jax writes their scopes vmap(...)
    "fusion.2": "jit(run_windows_v0)/while/body/drain/while/body/w32/"
                "vmap(nic.rx_admit)/vmap(tcp.rx)/select",
    "sort.3": "jit(run_windows_v0)/while/body/exchange/sort",
    "copy.4": "jit(run_windows_v0)/while/body/k8/copy",
    "reduce.5": "jit(run_windows_v0)/while/body/advance/reduce_min",
}


# another module whose instruction names collide with the window's
OTHER = {k: "jit(other)/exchange/x" for k in SCOPES}


@pytest.mark.parametrize("maps,modules", [
    ({"jit_run_windows_v0": SCOPES, "jit_other": OTHER},
     ("jit_run_windows_v0(42)",)),          # the module that ran
    ({"jit_run_windows_v0": SCOPES}, ()),    # one map, no module line
    ({"jit_run_windows_v0": SCOPES}, ("jit_f(7)",)),
])
def test_pass_label_shares(maps, modules):
    ctx = _trace_ctx(maps, modules)
    # self-times (us): drain 4,000 + rung-only 1,000; tcp.rx 2,000;
    # exchange 2,000 + advance 500; unlabelled 500; outside the slice
    # left out. Total 10,000
    assert _listed("drain_busy_frac").read(ctx) == pytest.approx(50.0)
    assert _listed("handler_busy_frac").read(ctx) == pytest.approx(20.0)
    assert _listed("exchange_busy_frac").read(ctx) == pytest.approx(25.0)
    assert ctx["_passes"]["labelled_frac"] == pytest.approx(0.95)


@pytest.mark.parametrize("path,label", [
    ("jit(f)/while/body/drain/while/body/w32/vmap(nic.rx_admit)/"
     "vmap(tcp.rx)/select", "tcp.rx"),          # innermost, peeled
    ("jit(f)/drain/dense/vmap(jit(_where))/select_n", "drain"),
    ("jit(f)/while/body/k8/copy", "drain"),      # a rung alone
    ("jit(f)/while/body/exchange.sharded/all-gather", "exchange.sharded"),
    ("jit(f)/while/body/cond/lt", None),
    (None, None),
])
def test_pass_label_rules(path, label):
    """The label rules are the benchmark's own (lib/passes.py)."""
    from perfbench.lib import passes
    assert passes.label_of(path) == label


@pytest.mark.parametrize("name", ["drain_busy_frac", "handler_busy_frac",
                                  "exchange_busy_frac"])
def test_pass_label_shares_missing(name):
    r = _listed(name)
    assert r.read(_trace_ctx({})) is None
    assert r.read(dict(_trace_ctx({"jit_run_windows_v0": SCOPES}),
                       trace=None)) is None
    # several maps and none of them ran: no way to tell them apart
    assert r.read(_trace_ctx({"jit_a": SCOPES, "jit_b": OTHER},
                             ("jit_c(1)",))) is None
