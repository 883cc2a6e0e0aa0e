"""The benchmark harness (perfbench/) on the CPU, at tiny sizes.

Covers what the chip runs cannot show cheaply: that cells, traffic
mixes and per-layer metrics are found by name; the whole-run window
arithmetic; the trace reduction against a recorded trace; the result
line's keys; that a run without a TPU exits non-zero and prints no
result; and that ``correct`` comes out false for the control and for
each planted fault of the timed path.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import compare, deploy, window  # noqa: E402
from perfbench.lib import spec as specmod  # noqa: E402
from perfbench.lib.trace import Trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "trace_fixture.pbtxt")
TINY_SEED = 2**31 + 11


@pytest.fixture(scope="module")
def spec():
    return specmod.Spec(ROOT)


def test_cells_and_metrics_found_by_name(spec):
    for name, cell in spec.workloads.items():
        assert spec.cell(name) is cell
        cfg, tr = spec.config(cell), spec.traffic(cell)
        assert cfg["name"] == cell["config"]
        assert tr["name"] == cell["traffic"]
        assert {m["name"] for m in spec.end_to_end(cell)} >= {
            "realtime_x", "hbm_peak_gb", "setup_s"}
        for m in spec.per_layer(cell):
            mod = specmod.reader(m)
            assert mod.MOVES == m["moves"] and mod.LAYER == m["layer"]
    with pytest.raises(specmod.SpecError):
        spec.cell("no-such-cell")


_PINS = re.compile(r"proxy-lo=(\d+) proxy-hi=(\d+) .*"
                   r"server-lo=(\d+) server-hi=(\d+)")


def _paths(dep):
    """-> sorted (client vertex, relay vertex, server vertex): the
    layout with the host ids taken out."""
    geo = [h["geocode"] for h in dep["hosts"]]
    out = []
    for i, h in enumerate(dep["hosts"]):
        m = _PINS.search(h["processes"][0]["args"])
        if m:
            p, p1, s, s1 = map(int, m.groups())
            assert (p1, s1) == (p + 1, s + 1)
            out.append((geo[i], geo[p], geo[s]))
    return sorted(out)


def test_deployment_is_built_from_the_seed(spec):
    socks = spec.cell("socks-2k-fetch")
    cfg, tr = spec.config(socks), spec.traffic(socks)
    a = deploy.build(cfg, tr, 7)
    assert len(a["hosts"]) == 2000
    assert all(h["quantity"] == 1 for h in a["hosts"])
    ids = [h["id"] for h in a["hosts"]]
    assert ids[:2] == ["server1", "server2"]
    assert ids[200] == "relay1" and ids[400] == "client1"
    args = a["hosts"][400]["processes"][0]["args"]
    p, _, s, _ = map(int, _PINS.search(args).groups())
    assert 200 <= p < 400 and 0 <= s < 200
    assert "size=51200 hops=1 count=1" in args
    assert "relay-lo=200 relay-hi=400" in a["hosts"][200]["processes"][0][
        "args"]
    assert a["hosts"][400]["processes"][0]["start_ns"] == 2 * 10**9
    assert a["stop_ns"] == 2_400_000_000
    b = deploy.build(cfg, tr, 7)
    c = deploy.build(cfg, tr, 8)
    assert a == b and a != c
    big = deploy.build(cfg, tr, 2**31 + 5)
    assert big["seed"] == 2**31 + 5


def test_the_seed_permutes_one_layout(spec):
    """Every seed gets the same paths and transfers, in another order
    of host ids."""
    socks = spec.cell("socks-2k-fetch")
    cfg, tr = spec.config(socks), spec.traffic(socks)
    a = deploy.build(cfg, tr, 11)
    b = deploy.build(cfg, tr, 3 * 10**9 + 7)
    assert [h["geocode"] for h in a["hosts"]] != [
        h["geocode"] for h in b["hosts"]]
    assert _paths(a) == _paths(b) and len(_paths(a)) == 1600
    moved = deploy.build(dict(cfg, layout_seed=2), tr, 11)
    assert _paths(moved) != _paths(a)


def test_tgen_roles_are_generated():
    cfg = {"name": "tgen-tiny", "hosts": 10, "stop_s": 3, "layout_seed": 3,
           "runahead_ms": 10, "engine_caps": {"qcap": 16},
           "topology": {"kind": "erdos_renyi", "vertices": 5, "edge_p": 0.5,
                        "seed": 1, "latency_ms_range": [5.0, 9.0],
                        "self_loop_ms": 1.0, "bandwidth_kib_s": 1024,
                        "loss": 0.0},
           "roles": [{"id": "server", "share": 0.2, "plugin": "tgen",
                      "tgen": {"listen": 30080}},
                     {"id": "web", "share": "rest", "plugin": "tgen"}]}
    tr = {"name": "walk", "roles": {
        "server": {"start_s": 1.0},
        "web": {"start_s": 2.0, "tgen": {"walk": {
            "peers": "server:30080", "type": "get", "size": "100 KiB",
            "pause": "1,2", "count": 0}}}}}
    d = deploy.build(cfg, tr, 2**32 + 9)
    assert [h["id"] for h in d["hosts"]] == (
        ["server1", "server2"] + [f"web{i}" for i in range(1, 9)])
    assert ">30080<" in d["hosts"][0]["processes"][0]["args"]
    walk = d["hosts"][2]["processes"][0]["args"]
    assert "server1:30080,server2:30080" in walk and "100 KiB" in walk
    assert d["seed"] == 9
    assert all(h["geocode"] in {f"v{i}" for i in range(5)}
               for h in d["hosts"])


def _plain_routes(graphml: str):
    """All-pairs path latency in ns, written from the GraphML alone: a
    heap Dijkstra over the cheapest edge between two vertices; a
    vertex reaches itself over its self-loop."""
    import heapq
    import xml.etree.ElementTree as ET
    ns = {"g": "http://graphml.graphdrawing.org/xmlns"}
    root = ET.fromstring(graphml.encode())
    key = {k.get("attr.name"): k.get("id") for k in root.iter(
        "{http://graphml.graphdrawing.org/xmlns}key") if k.get("for") == "edge"}
    ids = [n.get("id") for n in root.iterfind(".//g:node", ns)]
    at = {v: i for i, v in enumerate(ids)}
    V = len(ids)
    adj = [dict() for _ in range(V)]
    loop = [None] * V
    for e in root.iterfind(".//g:edge", ns):
        a, b = at[e.get("source")], at[e.get("target")]
        lat = next(float(d.text) for d in e.iterfind("g:data", ns)
                   if d.get("key") == key["latency"])
        if a == b:
            loop[a] = lat if loop[a] is None else min(loop[a], lat)
            continue
        for x, y in ((a, b), (b, a)):
            adj[x][y] = min(adj[x].get(y, lat), lat)
    out = np.zeros((V, V), np.int64)
    for s in range(V):
        dist, heap = {s: 0.0}, [(0.0, s)]
        while heap:
            d, u = heapq.heappop(heap)
            if d > dist[u]:
                continue
            for v, w in adj[u].items():
                if d + w < dist.get(v, float("inf")):
                    dist[v] = d + w
                    heapq.heappush(heap, (d + w, v))
        for v, d in dist.items():
            out[s, v] = round((loop[s] if v == s else d) * 1e6)
    return out


def test_routes_match_a_plain_dijkstra(spec):
    """A second witness for routing, which the reference copies from
    the program: both route tables equal a heap Dijkstra written here
    from the stand-in's GraphML."""
    from perfbench.reference.topology import build_topology as ref_build
    from shadow_tpu.routing.topology import build_topology as prog_build
    socks = spec.cell("socks-2k-fetch")
    cfg = spec.config(socks)
    dep = deploy.build(cfg, spec.traffic(socks), TINY_SEED)
    want = _plain_routes(dep["topology_graphml"])
    assert want.shape == (300, 300) and (want > 0).all()
    assert np.array_equal(ref_build(dep["topology_graphml"]).latency_ns, want)
    assert np.array_equal(prog_build(dep["topology_graphml"]).latency_ns,
                          want)


def test_reference_attaches_by_geocode(tiny):
    from perfbench.reference import world
    _, config, traffic = tiny
    dep = deploy.build(config, traffic, TINY_SEED)
    vertex = world.build(dep).hp.vertex
    assert [f"v{v}" for v in vertex] == [h["geocode"] for h in dep["hosts"]]


def test_window_holds_whole_runs():
    now = [0.0]

    def clock():
        return now[0]

    def build():
        now[0] += 0.5
        return object()

    def run(_):
        now[0] += 3.0
        return {"sim_ns": 2 * 10**9, "events": 100}

    w = window.run_window(build, run, 5.0, clock=clock)
    # the second run starts at 3.5 s (< 5) and finishes, and counts
    assert len(w["runs"]) == 2 and w["window_s"] == 7.0
    assert w["build_s"] == 1.0
    assert window.realtime_x(w["runs"], w["window_s"]) == 4.0 / 7.0
    assert window.events_per_s(w["runs"], w["window_s"]) == 200 / 7.0
    now[0] = 0.0
    w = window.run_window(build, run, 0.1, clock=clock)
    assert len(w["runs"]) == 1 and w["window_s"] == 3.5
    with pytest.raises(ValueError):
        window.run_window(build, run, 0, clock=clock)


def test_chunk_clock_times_chunks_between_records():
    now = [0.0]
    first = []
    clock = window.ChunkClock(clock=lambda: now[0],
                              on_first=lambda: first.append(now[0]))
    # run 1: chunk of 64 windows in 7 s, record 0.5 s, last chunk of 30
    # windows in 3 s, final record; run 2: one chunk of 50 in 5 s
    clock.start_run()
    for dt, edge, w in ((7, "start", 64), (0.5, "end", 64),
                        (3, "start", 94), (0.5, "end", 94)):
        now[0] += dt
        clock(edge, w)
    clock.start_run()
    now[0] += 5
    clock("start", 50)
    clock("end", 50)
    assert (clock.wall, clock.windows) == (15.0, 144)
    assert clock.record_wall == 1.0
    # the slice starts as the first record ends, not inside it
    assert first == [7.5]
    by_name = {m["name"]: m for m in specmod.Spec(ROOT).data["per_layer"]}
    ms = specmod.reader(by_name["chunk_ms_per_window"]).read(
        {"chunk_s": clock.wall, "chunk_windows": clock.windows})
    assert ms == 1000.0 * 15 / 144
    rec = specmod.reader(by_name["record_frac"])
    assert rec.read({"record_s": clock.record_wall, "window_s": 16.0}) == (
        100.0 / 16)
    assert rec.read({"window_s": 16.0}) is None


def _run(stats, digest="d0", overflow=0):
    return {"stats": stats, "digest": digest, "ovf": overflow}


@pytest.mark.parametrize("case,expect", [
    ("same", {}),
    ("cell", {"stat_cells_off": 1}),
    ("events", {"stat_cells_off": 1, "events_off": 3}),
    ("repeat_digest", {"repeats_off": 1}),
    ("repeat_stats", {"repeats_off": 1}),
    ("overflow", {"capacity_drops": 2}),
])
def test_compared_numbers(case, expect):
    names = ["ST_EVENTS", "ST_XFER_DONE"]
    ref = np.array([[10, 1], [20, 0]])
    prog = ref[:, ::-1].copy()       # the program's own column order
    prog_names = names[::-1]
    runs = [_run(prog.copy()), _run(prog.copy())]
    # "cell" and "events" change both runs alike: they still agree
    # with each other, only not with the reference
    if case == "cell":
        for r in runs:
            r["stats"][1, 0] += 1     # column 0 = ST_XFER_DONE
    elif case == "events":
        for r in runs:
            r["stats"][0, 1] += 3
    elif case == "repeat_digest":
        runs[1]["digest"] = "d1"
    elif case == "repeat_stats":
        runs[1]["stats"][0, 0] += 1
    elif case == "overflow":
        runs[1]["ovf"] = 2
    nums = compare.numbers(runs, prog_names, ref, names,
                           lambda r: r["ovf"])
    want = dict({k: 0 for k in compare.LIMITS}, **expect)
    assert nums == want
    assert compare.judge(nums) == (not expect)


def test_trace_reduction_on_the_recorded_trace():
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        tr = Trace.from_profile(ProfileData.from_text_proto(f.read()))
    assert tr.device_ids() == [0]
    lo, hi = tr.first_start(), tr.last_end()
    assert (lo, hi) == (0, 12_000_000)
    # ops [1,3)+[2,4) overlap -> 3 ms, [5,6.5) 1.5 ms, [9,10) 1 ms
    assert tr.busy_ns(lo, hi) == 5_500_000
    assert tr.busy_ns(2_000_000, 5_500_000) == 2_500_000
    # self-time: the overlapping fusions count once
    assert tr.device_ops(lo, hi) == [["fusion", 0.003],
                                     ["sort", 0.0015],
                                     ["reduce", 0.001]]
    assert tr.idle_gaps(lo, hi) == [["bench.run", 0.0025],
                                    ["bench.run", 0.002],
                                    ["bench.window", 0.001],
                                    ["bench.run", 0.001]]
    ctx = {"trace": tr, "trace_lo": lo, "trace_hi": hi,
           "busy_ns": tr.busy_ns(lo, hi)}
    spec = specmod.Spec(ROOT)
    by_name = {m["name"]: m for m in spec.data["per_layer"]}
    idle = specmod.reader(by_name["device_idle_frac"]).read(ctx)
    assert abs(idle - 100.0 * (1 - 5.5 / 12)) < 1e-9
    assert specmod.reader(by_name["device_idle_frac"]).read(
        dict(ctx, trace=None)) is None


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "socks-2k-fetch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=ROOT)
    assert p.returncode == 3
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


# --- the run itself, on a tiny deployment ----------------------------------

@pytest.fixture(scope="module")
def tiny(spec):
    """socks-2k-fetch's deployment cut to 10 hosts and 2.2 s."""
    cell = spec.cell("socks-2k-fetch")
    config = dict(spec.config(cell), hosts=10, stop_s=2.2)
    return cell, config, spec.traffic(cell)


def _measure(spec, tiny, trace=0):
    import jax
    from perfbench import run as runmod
    cell, config, traffic = tiny
    return runmod.measure(spec, cell, config, traffic, TINY_SEED, 0.01,
                          trace, jax.devices())


def test_result_line_and_correct_run(spec, tiny):
    out, lines = _measure(spec, tiny)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 1
    assert set(out["metrics"]) == {"realtime_x", "hbm_peak_gb", "setup_s"}
    assert all(m["value"] > 0 for k, m in out["metrics"].items()
               if k != "hbm_peak_gb")
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert [ln.split()[0] for ln in lines] == list(compare.LIMITS)
    json.dumps(out)

    out, _ = _measure(spec, tiny, trace=1)
    assert list(out)[-2:] == ["breakdown", "compared"]
    assert out["correct"] is True
    assert {"passes_per_window", "waste_frac", "build_frac",
            "chunk_ms_per_window", "record_frac"} <= set(out["metrics"])
    assert set(out["metrics"]) <= {m["name"] for m in spec.data["per_layer"]}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_is_not_correct(spec, tiny):
    """The reference with exact delivery broken, in the program's
    place, fails the comparison."""
    from perfbench.lib import system
    from perfbench.reference import netref
    from perfbench import run as runmod
    cell, config, traffic = tiny
    dep = deploy.build(config, traffic, TINY_SEED)
    res = system.run_once(system.Deployment(dep).build())
    ref, names = netref.run(dep)
    ok = compare.numbers([res], runmod.stat_names(), ref, names,
                         system.overflow)
    assert compare.judge(ok)
    ctl, names = netref.run(dep, control=True)
    bad = compare.numbers([res], runmod.stat_names(), ctl, names,
                          system.overflow)
    assert bad["stat_cells_off"] > 0 and not compare.judge(bad)


def _plant(kind):
    """A fault in the timed path: wraps the window-chunk step that
    Simulation.run calls."""
    import jax
    import jax.numpy as jnp
    from shadow_tpu.engine import defs
    from shadow_tpu.engine import sim as simmod
    real = simmod.run_windows

    def step(hosts, hp, sh, ws, we, cfg, chunk):
        before = jax.tree.map(jnp.copy, hosts)
        out = real(hosts, hp, sh, ws, we, cfg, chunk)
        new = out[0]
        if kind == "state_unchanged":
            new = before
        elif kind == "half_the_hosts":
            new = jax.tree.map(lambda a, b: a.at[1::2].set(b[1::2]),
                               new, before)
        elif kind == "answer_altered":
            new = new.replace(
                stats=new.stats.at[0, defs.ST_XFER_DONE].add(1))
        return (new,) + tuple(out[1:])

    return simmod, step


@pytest.mark.parametrize("kind", ["state_unchanged", "half_the_hosts",
                                  "answer_altered"])
def test_planted_fault_is_not_correct(spec, tiny, kind, monkeypatch):
    simmod, step = _plant(kind)
    monkeypatch.setattr(simmod, "run_windows", step)
    out, _ = _measure(spec, tiny)
    assert out["correct"] is False
    assert out["compared"]["stat_cells_off"]["value"] > 0
