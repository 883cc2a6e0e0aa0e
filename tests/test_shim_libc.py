"""Round-5 shim libc surface: poll/select, virtual time & sleep,
deterministic entropy, loud pthread_create refusal.

The reference's general libc emulation (process_emu_* backends,
/root/reference/src/main/host/shd-process.c:1821-7449) is what lets
arbitrary unmodified binaries run deterministically inside the sim.
These tests drive the round-5 additions through REAL compiled binaries
(examples/plugins/pollclient.c, libcprobe.c — plain libc, no simulator
headers), mirroring the reference's dual-build test pattern (SURVEY §4)
and its determinism dual-run
(src/test/determinism/shd-test-determinism.c:15-60).
"""

import os
import subprocess

import pytest

from shadow_tpu.core.config import HostSpec, ProcessSpec, Scenario
from shadow_tpu.engine import defs
from shadow_tpu.engine.sim import Simulation
from shadow_tpu.engine.state import EngineConfig

from test_shim import run_native_argv, TRANSFERS, NBYTES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLLCLIENT_C = os.path.join(REPO, "examples/plugins/pollclient.c")
LIBCPROBE_C = os.path.join(REPO, "examples/plugins/libcprobe.c")


@pytest.fixture(scope="module")
def pollclient_bin(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("shim") / "pollclient")
    subprocess.run(["cc", "-O2", "-o", out, POLLCLIENT_C], check=True)
    return out


@pytest.fixture(scope="module")
def libcprobe_bin(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("shim") / "libcprobe")
    subprocess.run(["cc", "-O2", "-o", out, LIBCPROBE_C, "-lpthread"],
                   check=True)
    return out


def _cfg(n=2):
    return EngineConfig(num_hosts=n, qcap=32, scap=8, obcap=16, incap=32,
                        txqcap=16, hostedcap=16, chunk_windows=8)


def _run_probe(libcprobe_bin, out_path, simple_topology_xml,
               sleep_ms=900, nrand=16, seed=1):
    scen = Scenario(
        stop_time=30 * 10**9,
        topology_graphml=simple_topology_xml,
        hosts=[HostSpec(id="probe", processes=[
            ProcessSpec(plugin="hosted:shim", start_time=10**9,
                        arguments=f"out={out_path} cmd={libcprobe_bin} "
                                  f"{sleep_ms} {nrand}")])],
    )
    report = Simulation(scen, engine_cfg=_cfg(1), seed=seed).run()
    with open(out_path) as f:
        return f.read(), report


def _parse(out):
    d = {}
    for line in out.splitlines():
        parts = line.split()
        for p in parts[1:]:
            k, _, v = p.partition("=")
            d[parts[0] + "." + k] = v
    return d


def test_sleep_advances_sim_time_and_clocks_agree(libcprobe_bin, tmp_path,
                                                  simple_topology_xml):
    """sleep()/usleep()/nanosleep() advance SIMULATED time (reference
    process_emu_nanosleep, shd-process.c:3055) and all three clock
    surfaces (clock_gettime / gettimeofday / time) read the same
    simulated clock (shd-process.c:4329-4389)."""
    import time as _t
    t0 = _t.perf_counter()
    out, _ = _run_probe(libcprobe_bin, str(tmp_path / "probe.out"),
                        simple_topology_xml, sleep_ms=900)
    wall = _t.perf_counter() - t0
    d = _parse(out)
    # the measured (simulated) sleep covers the request
    assert 0.85 <= float(d["slept.measured"]) <= 1.1, out
    # all clock surfaces agree on sim time (start_time = 1s)
    mono, real, tod = (float(d["clocks.mono"]), float(d["clocks.real"]),
                       float(d["clocks.tod"]))
    assert abs(mono - real) < 0.05 and abs(real - tod) < 0.05, out
    assert 0.9 <= mono <= 1.5, out
    assert int(d["clocks.time"]) in (0, 1, 2), out
    # ...and essentially none of it was wallclock: the 0.9s of
    # simulated sleeping must not burn 0.9s of real time sleeping
    # (generous bound — the run includes XLA dispatch overhead, but a
    # REAL sleep chain would add the full 0.9s on top)
    assert wall < 60, f"simulated sleep appears to burn wallclock: {wall}"


def test_entropy_determinism_dual_run(libcprobe_bin, tmp_path,
                                      simple_topology_xml):
    """The reference's determinism test, realized: an entropy-drawing
    binary (getrandom + /dev/urandom) runs TWICE under the sim with
    identical output — hosted entropy comes from the per-host seeded
    PRNG, not the kernel (shd-host.c:574,
    shd-test-determinism.c:15-60). A different seed changes the bytes
    (it is entropy, not zeros)."""
    out1, _ = _run_probe(libcprobe_bin, str(tmp_path / "p1.out"),
                         simple_topology_xml, seed=7)
    out2, _ = _run_probe(libcprobe_bin, str(tmp_path / "p2.out"),
                         simple_topology_xml, seed=7)
    assert out1 == out2, (out1, out2)
    d = _parse(out1)
    assert d["entropy.getrandom"] != "00" * 16, out1
    assert d["entropy.urandom"] != "00" * 16, out1
    assert d["entropy.getrandom"] != d["entropy.urandom"]
    # the stdio route (fopen/fread): glibc's fopen calls an INTERNAL
    # open, so only the fopen/fopen64 -> fopencookie interposition
    # keeps it deterministic (ADVICE r5); real PRNG bytes, advancing
    # the same host stream as the other draws, identical across the
    # dual run (out1 == out2 above covers the fentropy line too)
    assert d["fentropy.fopen"] != "00" * 16, out1
    assert d["fentropy.fopen"] != d["entropy.urandom"], out1

    out3, _ = _run_probe(libcprobe_bin, str(tmp_path / "p3.out"),
                         simple_topology_xml, seed=8)
    d3 = _parse(out3)
    assert d3["entropy.getrandom"] != d["entropy.getrandom"]


def test_urandom_write_refused_and_poll_sleep(libcprobe_bin, tmp_path,
                                              simple_topology_xml):
    """Round-5 advisor fixes, driven through a real binary: (a)
    write() to an entropy vfd fails cleanly with EBADF instead of
    forwarding OP_SEND and crashing shim.py with a KeyError; (b) the
    poll(NULL,0,ms) / select(0,...,&tv) sleep idioms advance
    SIMULATED time via OP_SLEEP (a real poll would freeze the virtual
    clock and wedge deadline loops)."""
    out, _ = _run_probe(libcprobe_bin, str(tmp_path / "uw.out"),
                        simple_topology_xml)
    d = _parse(out)
    assert int(d["urandomwrite.rc"]) == -1, out
    assert int(d["urandomwrite.errno"]) == 9, out   # EBADF
    # 150ms poll + 150ms select, measured on the simulated clock
    assert 0.25 <= float(d["pollsleep.measured"]) <= 0.45, out


def test_shim_op_metrics(libcprobe_bin, tmp_path, simple_topology_xml):
    """The preload protocol is metered: with the metrics registry on,
    a hosted run records per-op counts and latency histograms
    (obs.metrics shim section)."""
    from shadow_tpu.obs import metrics as M
    reg = M.install()
    try:
        out, _ = _run_probe(libcprobe_bin, str(tmp_path / "mt.out"),
                            simple_topology_xml)
        snap = reg.snapshot()
    finally:
        M.finish()
    assert "measured" in out
    ops = snap["shim"]["ops"]
    # the probe reads clocks, sleeps and draws entropy
    assert ops.get("clock", 0) > 0, ops
    assert ops.get("sleep", 0) > 0, ops
    assert ops.get("random", 0) > 0, ops
    lat = snap["shim"]["op_latency_us"]["clock"]
    assert lat["count"] == ops["clock"] and lat["mean"] > 0


def test_pthread_create_refused(libcprobe_bin, tmp_path,
                                simple_topology_xml):
    """pthread_create fails LOUDLY under the sim (EAGAIN=11) instead
    of silently spawning a real thread that would corrupt lockstep
    semantics (round-4 verdict item 9; the reference runs threads as
    rpth green threads, shd-process.c:5074-7449 — unimplemented
    here, so refusal is the only correct answer)."""
    out, _ = _run_probe(libcprobe_bin, str(tmp_path / "pt.out"),
                        simple_topology_xml)
    d = _parse(out)
    assert int(d["threads.pthread_create"]) == 11, out


def test_poll_select_client(pollclient_bin, tmp_path,
                            simple_topology_xml):
    """A poll()/select()-waiting binary — the wait style the round-4
    verdict called out as unsupported ('any poll()-based client
    fails') — completes the same transfers natively and simulated,
    and getsockname() reports real nonzero ports (not the round-4
    zeros)."""
    native = run_native_argv([pollclient_bin, "127.0.0.1", "{port}",
                              str(NBYTES), str(TRANSFERS)])
    assert f"transfers={TRANSFERS} bytes={NBYTES * TRANSFERS}" in native

    out_path = str(tmp_path / "pollclient.out")
    scen = Scenario(
        stop_time=60 * 10**9,
        topology_graphml=simple_topology_xml,
        hosts=[
            HostSpec(id="server", processes=[
                ProcessSpec(plugin="bulkserver", start_time=10**9,
                            arguments="port=8080")]),
            HostSpec(id="client", processes=[
                ProcessSpec(plugin="hosted:shim", start_time=2 * 10**9,
                            arguments=f"out={out_path} "
                                      f"cmd={pollclient_bin} "
                                      f"server 8080 {NBYTES} "
                                      f"{TRANSFERS}")]),
        ],
    )
    report = Simulation(scen, engine_cfg=_cfg()).run()
    with open(out_path) as f:
        sim_out = f.read()
    assert (f"transfers={TRANSFERS} bytes={NBYTES * TRANSFERS}"
            in sim_out), sim_out
    assert f"ports_ok={TRANSFERS}" in sim_out, sim_out
    assert report.stats[0, defs.ST_XFER_DONE] == TRANSFERS
    assert report.stats[0, defs.ST_BYTES_RECV] == NBYTES * TRANSFERS
