"""Observability tests: span tracing, metrics registry, heartbeat
tracker, pcap capture, logger, and one timeline: the run loop's spans
on the profiler's clock and the window program's pass labels read from
its own executable."""

import glob
import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.core.config import HostSpec, ProcessSpec, Scenario
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.engine.state import EngineConfig
from shadow_tpu.obs import metrics as M
from shadow_tpu.obs import trace as T
from shadow_tpu.obs.logger import SimLogger

from test_phold import MESH_TOPO


@pytest.fixture(autouse=True)
def _obs_globals_reset():
    """The trace/metrics recorders are process-global; a test that
    fails mid-install must not leak an enabled recorder into the next
    test."""
    yield
    T.finish()
    M.finish()


def scen(pcap=False, stop=4):
    return Scenario(
        stop_time=stop * 10**9,
        topology_graphml=MESH_TOPO,
        hosts=[
            HostSpec(id="srv", pcap=pcap, processes=[
                ProcessSpec(plugin="pingserver", start_time=10**9,
                            arguments="port=8000")]),
            HostSpec(id="cli", pcap=pcap, processes=[
                ProcessSpec(plugin="ping", start_time=2 * 10**9,
                            arguments="peer=srv port=8000 interval=500ms "
                                      "size=100 count=3")]),
        ],
    )


CFG = dict(qcap=16, scap=4, obcap=8, incap=16, chunk_windows=8)


def test_heartbeat_lines():
    sim = Simulation(scen(stop=6), engine_cfg=EngineConfig(num_hosts=2, **CFG))
    report = sim.run(heartbeat_s=1.0)
    node_lines = [l for l in report.heartbeats if "[node]" in l]
    summaries = [l for l in report.heartbeats if "[summary]" in l]
    # several intervals may elapse within one window chunk; each
    # summary then carries the covered span in its interval= field,
    # and the spans tile the whole simulated time
    assert summaries
    spans = [int(l.split("interval=")[1].split(",")[0]) for l in summaries]
    assert sum(spans) >= 5
    assert any(",cli," in l for l in node_lines)
    # [socket] lines: ping's UDP sockets appear with peer and buffers
    sock_lines = [l for l in report.heartbeats if "[socket]" in l]
    assert any(",cli," in l and "udp" in l for l in sock_lines)


def test_heartbeat_socket_ram_tcp():
    """TCP heartbeats carry tcp [socket] segments and, while the send
    buffer holds unacked bytes, per-host [ram] occupancy lines
    (the reference's per-socket buffer-fill + allocated-RAM heartbeat,
    shd-tracker.c:449-546)."""
    from test_tcp import bulk_scenario, poi_topology
    sim = Simulation(
        bulk_scenario(poi_topology(bw_up=1024), size=400_000, count=1,
                      stop=8),
        engine_cfg=EngineConfig(num_hosts=2, qcap=16, scap=4, obcap=32,
                                incap=32, chunk_windows=8))
    report = sim.run(heartbeat_s=0.5)
    sock_lines = [l for l in report.heartbeats if "[socket]" in l]
    assert any("tcp" in l for l in sock_lines)
    ram_lines = [l for l in report.heartbeats if "[ram]" in l]
    # the 400 KB push over a 1 MB/s uplink keeps unacked bytes in the
    # send buffer across several 0.5s intervals
    assert ram_lines
    # schema: t,host,alloc,dealloc,total,sockets — total > 0 somewhere
    assert any(int(l.split(",")[4]) > 0 for l in ram_lines)
    # parse tool roundtrip
    import subprocess, sys, tempfile, os
    with tempfile.NamedTemporaryFile("w", suffix=".log", delete=False) as f:
        f.write("\n".join(report.heartbeats))
        path = f.name
    out = subprocess.run(
        [sys.executable, "tools/parse_heartbeat.py", path],
        capture_output=True, text=True, check=True).stdout
    assert out.splitlines()[0].startswith("time,host")
    assert any("cli" in l for l in out.splitlines()[1:])
    os.unlink(path)


def test_pcap_capture(tmp_path):
    sim = Simulation(scen(pcap=True),
                     engine_cfg=EngineConfig(num_hosts=2, **CFG))
    assert sim.cfg.tracecap > 0  # auto-sized because logpcap is set
    sim.run(pcap_dir=str(tmp_path))

    cli = tmp_path / "cli-eth0.pcap"
    srv = tmp_path / "srv-eth0.pcap"
    assert cli.exists() and srv.exists()

    data = cli.read_bytes()
    magic, _, _, _, _, snaplen, network = struct.unpack("<IHHiIII",
                                                        data[:24])
    assert magic == 0xA1B2C3D4
    assert network == 1  # Ethernet
    # walk the records: client sent 3 pings (tx) and got 3 echoes (rx)
    off, n, lens = 24, 0, []
    while off < len(data):
        ts, tus, incl, orig = struct.unpack("<IIII", data[off:off + 16])
        lens.append(orig)
        off += 16 + incl
        n += 1
    assert n == 6
    # udp: 14 eth + 20 ip + 8 udp + 100 payload
    assert all(l == 142 for l in lens)


def test_trace_span_nesting(tmp_path):
    """Nested spans flush as valid Chrome trace-event JSON: complete
    ("X") events with µs ts/dur, children contained in parents, args
    preserved."""
    path = str(tmp_path / "t.json")
    T.install(path)
    with T.span("outer", kind="test"):
        with T.span("inner"):
            pass
        t0 = T.TRACER.now()
        T.TRACER.complete("hot", t0, args={"n": 3})
    T.finish()
    assert not T.ENABLED and T.TRACER is None

    doc = json.load(open(path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "hot"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ts"] <= inner["ts"]
    assert (inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-6)
    assert outer["args"] == {"kind": "test"}
    assert by_name["hot"]["args"] == {"n": 3}
    # metadata names the process for Perfetto
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])


def test_trace_begin_end_on_both_sinks(monkeypatch):
    """A begin/end span lands in the JSON sink and, as a profiler
    TraceAnnotation of the same name and args, on the profiler's
    clock; the tracer's tags join every span begun under them; with
    nothing installed the cold-path helpers are no-ops."""
    made = []

    class Ann:
        def __init__(self, name, **kw):
            self.name, self.kw, self.state = name, dict(kw), "new"
            made.append(self)

        def __enter__(self):
            self.state = "open"
            return self

        def __exit__(self, *exc):
            self.state = "closed"

        def set_metadata(self, **kw):
            self.kw.update(kw)

    monkeypatch.setattr(T, "_annotation_class", lambda: Ann)
    assert T.begin("never") is None
    T.end(None)
    tr = T.install(None)
    tr.tags = {"run": 7}
    tok = tr.begin("chunk", sim_ns_start=1)
    inner = T.begin("chunk.wait")
    assert [a.state for a in made] == ["open", "open"]
    T.end(inner)
    tr.end(tok, windows=3)
    T.finish()
    assert [(a.name, a.state) for a in made] == [("chunk", "closed"),
                                                 ("chunk.wait", "closed")]
    assert made[0].kw == {"run": 7, "sim_ns_start": 1, "windows": 3}
    by = {e["name"]: e for e in tr.events}
    assert by["chunk"]["args"] == made[0].kw
    assert by["chunk.wait"]["args"] == {"run": 7}
    c, w = by["chunk"], by["chunk.wait"]
    assert c["ts"] <= w["ts"] and w["ts"] + w["dur"] <= c["ts"] + c["dur"]


def test_trace_disabled_span_is_noop(tmp_path):
    """With nothing installed the module stays disabled and span() is
    a pass-through — the contract the hot-loop boolean guards rely
    on."""
    assert not T.ENABLED
    with T.span("never"):
        pass
    assert T.TRACER is None


def test_metrics_registry_semantics():
    """Counter/gauge/histogram semantics and the snapshot shape."""
    reg = M.install()
    try:
        reg.counter("a").inc()
        reg.counter("a").inc(4)
        reg.gauge("g").set(2.5)
        h = reg.histogram("h", bounds=(10, 100))
        for v in (1, 9, 10, 11, 250):
            h.observe(v)
        M.shim_op("send", 5_000)     # 5 µs
        M.shim_op("send", 7_000)
        snap = reg.snapshot()
    finally:
        M.finish()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 5 and hs["min"] == 1 and hs["max"] == 250
    assert hs["sum"] == 281
    # bisect_left semantics: <=10 in the first bucket, 11 in the
    # second, 250 overflows
    assert hs["buckets"] == {"le_10": 3, "le_100": 1, "overflow": 1}
    # the shim per-op aggregation view
    assert snap["shim"]["ops"] == {"send": 2}
    lat = snap["shim"]["op_latency_us"]["send"]
    assert lat["count"] == 2 and 5 <= lat["mean"] <= 7


def test_run_trace_metrics_smoke(tmp_path):
    """A small ping run with trace+metrics produces (a) a loadable
    trace with >= 4 distinct span names whose chunk spans carry
    sim_ns_start/sim_ns_end/events args, and (b) a metrics snapshot
    with events/sec, wall per sim-second and the shim section — the
    PR's acceptance shape."""
    tr_path = str(tmp_path / "trace.json")
    mt_path = str(tmp_path / "metrics.json")
    sim = Simulation(scen(stop=6),
                     engine_cfg=EngineConfig(num_hosts=2, **CFG))
    report = sim.run(heartbeat_s=1.0, trace=tr_path, metrics=mt_path)
    # recorders are torn down with the run
    assert not T.ENABLED and not M.ENABLED

    doc = json.load(open(tr_path))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    assert {"chunk", "compile+first_chunk", "run.setup",
            "report.finalize", "tracker.heartbeat"} <= names
    assert len(names) >= 4, names
    chunks = [e for e in evs if e["name"] == "chunk"]
    assert chunks
    for c in chunks:
        a = c["args"]
        assert {"sim_ns_start", "sim_ns_end", "windows",
                "events"} <= set(a)
        assert a["sim_ns_end"] >= a["sim_ns_start"]
    # chunk events tally with the report
    assert sum(c["args"]["events"] for c in chunks) == report.events
    assert sum(c["args"]["windows"] for c in chunks) == report.windows

    snap = json.load(open(mt_path))
    assert snap["sim"]["events"] == report.events
    assert snap["sim"]["events_per_sec"] > 0
    assert "wall_per_sim_second" in snap["sim"]
    assert "ops" in snap["shim"]            # present (empty: no shim)
    assert snap["counters"]["engine.windows"] == report.windows
    # tracker heartbeats surface through the registry
    assert snap["counters"]["tracker.heartbeats"] >= 1
    assert snap["counters"]["tracker.lines"] == len(report.heartbeats)

    # per-chunk JSON lines parse and tile the run
    lines = [json.loads(l) for l in
             open(mt_path + ".chunks.jsonl").read().splitlines()]
    assert len(lines) == snap["counters"]["engine.chunks"]
    assert sum(l["events"] for l in lines) == report.events


def test_trace_report_tool(tmp_path):
    """tools/trace_report.py end-to-end on a real run's trace: the
    headless CPU path the CI satellite asks for."""
    import os
    import subprocess
    import sys
    tr_path = str(tmp_path / "trace.json")
    sim = Simulation(scen(stop=4),
                     engine_cfg=EngineConfig(num_hosts=2, **CFG))
    sim.run(trace=tr_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools/trace_report.py"),
         tr_path],
        capture_output=True, text=True, check=True).stdout
    assert "top spans by self-time" in out
    assert "chunk" in out
    assert "wall per sim-second" in out
    # --json mode round-trips
    js = subprocess.run(
        [sys.executable, os.path.join(repo, "tools/trace_report.py"),
         tr_path, "--json"],
        capture_output=True, text=True, check=True).stdout
    rep = json.loads(js)
    assert rep["chunks"] and rep["spans"]
    assert any(s["name"] == "chunk" for s in rep["spans"])


def test_trace_report_bad_input(tmp_path):
    """Missing / empty / truncated / non-trace input: a one-line
    diagnosis on stderr and a nonzero exit — never a traceback
    (headless tool robustness satellite)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools/trace_report.py")
    empty = tmp_path / "empty.json"
    empty.write_text("")
    trunc = tmp_path / "trunc.json"
    trunc.write_text('{"traceEvents": [{"name": "chunk", "ph": "X"')
    nontrace = tmp_path / "nontrace.json"
    nontrace.write_text('{"foo": 1}')
    noevents = tmp_path / "noevents.json"
    noevents.write_text('{"traceEvents": []}')
    for bad in (str(tmp_path / "missing.json"), str(empty),
                str(trunc), str(nontrace), str(noevents)):
        out = subprocess.run([sys.executable, tool, bad],
                             capture_output=True, text=True)
        assert out.returncode != 0, bad
        assert "Traceback" not in out.stderr, (bad, out.stderr)
        msg = out.stderr.strip()
        assert msg.startswith("trace_report:") and "\n" not in msg, bad


def test_pyengine_trace_and_metrics(tmp_path):
    """The differential oracle's event loop shows up on the same
    timeline (pyengine.window spans) and in the registry."""
    from shadow_tpu.engine.pyengine import PyEngine
    path = str(tmp_path / "py.json")
    T.install(path)
    reg = M.install()
    try:
        sim = Simulation(scen(stop=4),
                         engine_cfg=EngineConfig(num_hosts=2, **CFG))
        stats = PyEngine(sim).run()
    finally:
        tr = T.finish()
        M.finish()
    names = [e["name"] for e in tr.events]
    assert "pyengine.window" in names
    from shadow_tpu.engine import defs
    ev = int(stats[:, defs.ST_EVENTS].sum())
    assert reg.counters["pyengine.events"].n == ev
    assert reg.counters["pyengine.windows"].n > 0


def test_logger_levels(capsys):
    lg = SimLogger(level="message")
    lg.message(1_500_000_000, "hostA", "hello")
    lg.debug(2_000_000_000, "hostA", "invisible")
    lg.set_host_level("chatty", "debug")
    lg.debug(2_000_000_000, "chatty", "visible")
    out = capsys.readouterr().out
    assert "hello" in out and "0:00:01.500000000" in out
    assert "invisible" not in out
    assert "visible" in out


def test_capacity_report(simple_topology_xml):
    """End-of-run capacity accounting (the ObjectCounter analogue):
    peaks reflect real occupancy and no overflow on a healthy run."""
    from shadow_tpu.core.config import HostSpec, ProcessSpec, Scenario
    from shadow_tpu.engine.sim import Simulation

    scen = Scenario(
        stop_time=5 * 10**9,
        topology_graphml=simple_topology_xml,
        hosts=[
            HostSpec(id="server", processes=[
                ProcessSpec(plugin="pingserver", start_time=10**9,
                            arguments="port=9000")]),
            HostSpec(id="client", processes=[
                ProcessSpec(plugin="ping", start_time=10**9,
                            arguments="peer=server port=9000 "
                                      "interval=100ms count=10")]),
        ],
    )
    report = Simulation(scen).run()
    rows = {r["array"]: r for r in report.capacity_report()}
    assert set(rows) == {"event_queue", "socket_table", "outbox",
                         "nic_txq"}
    # the ping exchange touched the queue, sockets and outbox
    assert rows["event_queue"]["peak"] >= 1
    assert rows["socket_table"]["peak"] >= 1
    assert rows["outbox"]["peak"] >= 1
    for r in rows.values():
        assert r["peak"] <= r["capacity"]
        assert r["overflow"] == 0


def test_delivery_status_trail(tmp_path):
    """Packets carry the reference's delivery-status trail
    (shd-packet.h:15-36 recast as a bitmask word): trace records show
    the lifecycle stages each packet passed through."""
    import numpy as np
    from shadow_tpu.net import packet as P

    sim = Simulation(scen(pcap=True),
                     engine_cfg=None)
    sim.run()  # no pcap_dir: trace rings retain the records
    h = sim.final_hosts
    cnt = np.asarray(h.tr_cnt)
    assert cnt.sum() > 0
    pkts = np.asarray(h.tr_pkt)
    dirs = np.asarray(h.tr_dir)
    saw_tx = saw_rx = False
    for hid in range(cnt.shape[0]):
        for k in range(cnt[hid]):
            st = int(pkts[hid, k, P.STATUS])
            names = P.status_names(st)
            assert "created" in names
            assert "nic-sent" in names
            assert "inet" in names  # exchange-traced = cross-host
            if dirs[hid, k] == 1:
                saw_tx = True
            else:
                saw_rx = True
    assert saw_tx and saw_rx


# --- one timeline ------------------------------------------------------
#
# obs.trace's begin/end spans are also jax.profiler TraceAnnotations, so
# a profiled run's xspace host plane holds the same spans as the JSON
# sink; core.jitcache.AotJit.scope_map maps each executable's HLO
# instruction names to their named_scope paths (obs.passcope.
# scope_map_of), the map a trace taken without HLO protos lacks. These
# tests run the ping program the tests above compile (CFG).

SPANS = ("chunk", "chunk.dispatch", "chunk.wait", "digest.record",
         "digest.pull", "digest.hash")


def _ping_sim():
    return Simulation(scen(stop=6),
                      engine_cfg=EngineConfig(num_hosts=2, **CFG))


def _profile(tmp, fn, hlo=True):
    """Run fn() under jax.profiler -> the .xplane.pb path."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = hlo
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return glob.glob(os.path.join(str(tmp), "**", "*.xplane.pb"),
                     recursive=True)[0]


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    """The ping program run with no tracer installed, counting every
    annotation made and every scope map built."""
    from shadow_tpu.engine.window import run_windows_aot
    from shadow_tpu.obs import passcope
    made = {"ann": 0, "maps": 0}
    real_ann, real_map = jax.profiler.TraceAnnotation, passcope.scope_map_of

    class Counting(real_ann):
        def __init__(self, *a, **kw):
            made["ann"] += 1
            super().__init__(*a, **kw)

    def counting_map(compiled):
        made["maps"] += 1
        return real_map(compiled)
    mp = pytest.MonkeyPatch()
    mp.setattr(jax.profiler, "TraceAnnotation", Counting)
    mp.setattr(passcope, "scope_map_of", counting_map)
    try:
        assert not T.ENABLED
        sim = _ping_sim()
        report = sim.run(digest=str(tmp_path_factory.mktemp("dg") / "c"))
        prog = run_windows_aot(sim.cfg, sim.effective_chunk(64))
        return made, report, prog
    finally:
        mp.undo()


def test_tracer_off_makes_no_annotation_and_no_scope_map(untraced):
    from shadow_tpu.core.jitcache import AotJit
    made, report, prog = untraced
    assert report.events > 0 and prog._compiled
    assert made == {"ann": 0, "maps": 0}
    # a program materialised with tracing off holds no map
    fresh = AotJit(lambda x: jnp.cumsum(x) * 2)
    fresh(jnp.arange(7))
    assert fresh._compiled and fresh._scopes is None


def test_spans_on_the_profiler_clock_match_the_json_sink(untraced,
                                                         tmp_path):
    from jax.profiler import ProfileData
    T.install(None)
    try:
        xp = _profile(tmp_path, lambda: _ping_sim().run(
            digest=str(tmp_path / "chain")), hlo=False)
    finally:
        tr = T.finish()
    js = sorted((e for e in tr.events if e["name"] in SPANS),
                key=lambda e: (e["ts"], -e["dur"]))
    host = []
    for plane in ProfileData.from_file(xp).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in line.events
                         if e.name.split("#")[0] in SPANS]
    host.sort(key=lambda e: (e.start_ns, -e.duration_ns))
    names = [e["name"] for e in js]
    assert names == [e.name.split("#")[0] for e in host]
    assert set(names) == set(SPANS)
    # one run: every span carries the run's id and its chunk
    runs = {e["args"]["run"] for e in js}
    assert len(runs) == 1
    assert all("chunk" in e["args"] for e in js)
    pulls = [e for e in js if e["name"] == "digest.pull"]
    assert all(e["args"]["bytes"] > 0 for e in pulls)
    # the same durations within a tolerance, and the same nesting
    t0_js, t0_host = js[0]["ts"], host[0].start_ns
    for j, h in zip(js, host):
        assert abs(j["dur"] - h.duration_ns / 1e3) <= 0.05 * j["dur"] + 200
        assert abs((j["ts"] - t0_js)
                   - (h.start_ns - t0_host) / 1e3) <= 1000

    def inside(a, b, s, d):      # span a (start, dur) within span b
        return s(b) <= s(a) and s(a) + d(a) <= s(b) + d(b) + 1

    for kid, parent in (("chunk.dispatch", "chunk"),
                        ("chunk.wait", "chunk"),
                        ("digest.pull", "digest.record"),
                        ("digest.hash", "digest.record")):
        for evs, s, d, nm in (
                (js, lambda e: e["ts"], lambda e: e["dur"],
                 lambda e: e["name"]),
                (host, lambda e: e.start_ns / 1e3,
                 lambda e: e.duration_ns / 1e3,
                 lambda e: e.name.split("#")[0])):
            ps = [e for e in evs if nm(e) == parent]
            for k in (e for e in evs if nm(e) == kid):
                assert any(inside(k, p, s, d) for p in ps), (kid, parent)


def test_scope_map_of_equals_the_trace_scope_map(untraced, tmp_path):
    """The executable's own HLO names its ops' scopes exactly as the
    HLO proto a trace embeds does."""
    from shadow_tpu.core.simtime import SIMTIME_MAX
    from shadow_tpu.engine.window import (chunk_program_scopes,
                                          run_windows_aot)
    from shadow_tpu.obs import passcope
    _, _, prog = untraced
    sim = _ping_sim()
    chunk = sim.effective_chunk(64)
    # the run loop's own first window: the signature it compiled for
    t0 = jnp.min(sim.hosts.eq_next)
    args = (sim.hosts, sim.hp, sim.sh, t0,
            jnp.where(t0 == SIMTIME_MAX, t0, t0 + sim.sh.min_jump))
    exe = run_windows_aot(sim.cfg, chunk).warm(*args)
    jax.block_until_ready(args)
    xp = _profile(tmp_path, lambda: jax.block_until_ready(exe(*args)))
    (module, mine), = passcope.scope_map_of(exe).items()
    assert mine and passcope.hlo_scope_map(xp, module) == mine
    labels = {p for op in mine.values() if op for p in op.split("/")}
    assert {"drain", "exchange", "advance"} <= labels
    # the on-request maps over the process's chunk programs hold it,
    # under its module's name: the programs' instruction names collide
    assert prog.scope_map()[module] == mine
    assert chunk_program_scopes()[module] == mine


def test_scope_map_built_on_request():
    """A run builds no scope map, traced or not: AotJit.scope_map()
    reads it on request, once per executable."""
    from shadow_tpu.core.jitcache import AotJit

    def f(x):
        with jax.named_scope("exchange"):
            return jnp.cumsum(x) * 2

    off, on = AotJit(f), AotJit(f)
    off(jnp.arange(7))
    T.install(None)
    try:
        on(jnp.arange(7))
    finally:
        T.finish()
    assert off._scopes is None and on._scopes is None
    m = on.scope_map()
    (module, ops), = m.items()
    assert any("exchange" in (op or "") for op in ops.values())
    assert len(on._scopes) == 1
    assert off.scope_map() == m and on.scope_map() == m
