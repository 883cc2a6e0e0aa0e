"""Simulation: scenario -> device state -> run loop -> report.

This is the L6/L7 equivalent of the reference's Master/Slave
(/root/reference/src/main/core/shd-master.c, shd-slave.c): it loads the
scenario, builds the topology oracle and DNS, registers hosts and their
processes, then drives the window loop. There is no worker-thread
machinery to manage — the "scheduler" is the compiled window program of
engine.window.
"""

from __future__ import annotations

import itertools as _itertools
import time as _time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as R
from ..core.config import Scenario
from ..core.constants import DEFAULT_MIN_TIME_JUMP, INTERFACE_BUFFER_SIZE
from ..core.simtime import SIMTIME_MAX, SIMTIME_ONE_SECOND
from ..routing.dns import DNS
from ..routing.topology import Topology, attach_hosts, build_topology
from ..apps.compile import compile_app
from ..net.packet import PKT_WORDS
from ..obs import trace as TR
from . import defs
from .defs import EV_APP, WAKE_START, N_STATS
from .state import (EngineConfig, Hosts, HostParams, Shared,
                    alloc_hosts, hot_fields, make_shared)
from .window import run_windows
from ..net import packet as P


class Preempted(RuntimeError):
    """Raised by run() when a cooperative preemption was requested
    (request_preempt — the CLI's SIGTERM handler under --checkpoint,
    the fleet worker's preemption protocol): the loop stopped at the
    next chunk boundary, saving a snapshot there first when a
    checkpoint store is active, so ``--resume latest`` continues the
    run with zero lost work. The CLI maps this to exit status 75
    (EX_TEMPFAIL, engine.supervisor.EXIT_PREEMPTED): "resumable, try
    again" — supervisors and the fleet scheduler requeue instead of
    counting a crash."""

    def __init__(self, sim_ns: int, saved: bool):
        self.sim_ns = int(sim_ns)
        self.saved = bool(saved)
        what = ("snapshot saved" if saved
                else "no checkpoint store — nothing saved")
        super().__init__(f"run preempted at sim_ns={sim_ns} ({what})")


# process-wide cooperative-preemption flag: signal-handler-safe (a
# plain Event), observed by every running Simulation at its next chunk
# boundary. run() clears it on entry so a flag left by a previous
# run's preemption cannot kill the next run in the same process.
import threading as _threading                              # noqa: E402

_PREEMPT = _threading.Event()


def request_preempt():
    """Ask the running simulation to checkpoint at the next chunk
    boundary and raise Preempted. Safe to call from a signal handler
    or another thread; a no-op until a run loop observes it."""
    _PREEMPT.set()


# the traced runs of this process, numbered: the ``run`` arg of every
# span a traced run opens (obs.trace tags), so one run's spans share
# an identifier on a timeline holding several runs
_RUN_SEQ = _itertools.count(1)

# jitted once per process, not per run: a fresh jax.jit(lambda) in
# every run would retrace (and look up or compile) inside the run
# loop. _zeros_like resets the pcap ring on a multi-process mesh.
_zeros_like = jax.jit(jnp.zeros_like)


@jax.jit
def _ev_sum(stats):
    """Events executed so far, as a jitted reduction: a replicated
    scalar on every process (eager ops cannot run on non-addressable
    global arrays) and one column's sum instead of a full stats
    gather. Padded inert rows never execute events, so the all-rows
    sum equals [:H]."""
    return jnp.sum(stats[:, defs.ST_EVENTS])


@dataclass
class SimReport:
    """Aggregated results of a run."""
    stats: np.ndarray          # [H, N_STATS]
    host_names: list
    sim_time_ns: int
    wall_seconds: float
    windows: int
    heartbeats: list = field(default_factory=list)
    capacity: dict = field(default_factory=dict)
    cost: dict = field(default_factory=dict)  # cost_model() inputs:
    #   pass mix per compaction rung, per-row state bytes, warm wall
    memory: dict = field(default_factory=dict)  # memory observatory
    #   record (obs.memscope): device-buffer watermark (peak_bytes /
    #   source / per_device), the state byte census totals
    #   (state_bytes, state_bytes_per_host, hot_state_bytes) and the
    #   window program's captured XLA analysis under "xla" (flops,
    #   bytes_accessed, argument/temp/output bytes — None entries
    #   where the backend refused)
    network: dict = field(default_factory=dict)  # network observatory
    #   record (obs.netscope, cfg.netscope runs only): per-kind
    #   (rtt/completion/queue/retx) bucket counts with exact
    #   p50/p90/p99 read-outs from the device-side histograms, plus
    #   the bucket bounds and — when run(netscope=...) streamed a
    #   JSONL time-series — the record count and path
    device_phases: dict = field(default_factory=dict)  # passcope
    #   observatory record (obs.passcope, --passcope runs only): the
    #   per-pass device-time table decoded from the profiler's xplane
    #   files — {phases: {stateflow label: {ms, frac}}, rungs,
    #   attributed_frac, residual_*} with available: False + the error
    #   on backends that refuse the profiler
    occupancy: dict = field(default_factory=dict)  # lockstep-
    #   efficiency record (obs.passcope.occupancy, always on —
    #   computed from the drain's own pass counters): lane_steps,
    #   utilization, waste_frac, per_rung min-fill bounds, and — on
    #   mesh runs — the per-shard waste view under "shards"
    hosted: dict = field(default_factory=dict)  # hosted-process exit
    #   report: host name -> {"exit_status", "cause", "sim_ns"} from
    #   the shim supervisor (hosting.runtime.exit_info) — the per-host
    #   exit status + cause the robustness layer guarantees even when
    #   a child crashes/hangs mid-run
    faults: list = field(default_factory=list)  # applied fault events
    #   in execution order (engine.faults.FaultInjector.log)

    def total(self, stat: int) -> int:
        return int(self.stats[:, stat].sum())

    @property
    def events(self) -> int:
        return self.total(defs.ST_EVENTS)

    @property
    def events_per_sec(self) -> float:
        return self.events / self.wall_seconds if self.wall_seconds else 0.0

    @property
    def speedup(self) -> float:
        """Simulated seconds per wallclock second."""
        if not self.wall_seconds:
            return 0.0
        return (self.sim_time_ns / SIMTIME_ONE_SECOND) / self.wall_seconds

    def capacity_report(self) -> list:
        """End-of-run capacity accounting (the reference's
        ObjectCounter shutdown report, shd-slave.c:207-211, recast for
        fixed arrays): per array, configured capacity, peak occupancy
        across hosts, and events lost to overflow — each overflow
        class named separately. Cross-host arrivals never drop on
        capacity (they defer at the source, `deferred` column); the
        drop columns cover local pushes and the NIC rings only."""
        drops = {
            "event_queue": (self.total(defs.ST_PKTS_DROP_Q) +
                            self.total(defs.ST_EQ_FULL_LOCAL)),
            "socket_table": self.total(defs.ST_SOCK_FAIL),
            "outbox": self.total(defs.ST_OUTBOX_DROP),
            "nic_txq": self.total(defs.ST_TXQ_DROP),
        }
        defers = {
            "event_queue": self.total(defs.ST_DEFER_FANIN),
            "outbox": self.total(defs.ST_DEFER_A2A),
        }
        out = []
        for name, cap, peak in self.capacity.get("rows", []):
            out.append({"array": name, "capacity": cap, "peak": peak,
                        "overflow": drops.get(name, 0),
                        "deferred": defers.get(name, 0)})
        return out

    def cost_model(self) -> dict:
        """Where the wall time goes, in pass-mix and modeled HBM-
        traffic terms — the per-pass cost model the round-3 verdict
        asked for (the reference self-reports the analogous numbers:
        scheduler idle/barrier-wait seconds shd-scheduler.c:250-252,
        per-host exec seconds shd-host.c:201-208). On this hardware
        the pass cost is row-state HBM traffic, so the model reports
        bytes moved per pass per rung and the achieved bandwidth
        against the chip's roofline.

        All byte figures are MODELED from array shapes (gather/step/
        scatter traffic assuming no fusion savings), not measured
        counters — upper bounds that localize where the time goes;
        `achieved_gbps_est` divides PER-CHIP modeled traffic by the
        warm wall (excluding the first chunk's compile when the run
        had more than one chunk — `warm` says which), so it reads as
        "what fraction of the roofline would this run sustain if the
        model were exact"."""
        if not self.cost:
            return {}
        rb = self.cost["row_bytes"]
        mix = self.cost["pass_mix"]       # {label: (K or H, passes)}
        B = self.cost.get("batch", {})    # {label: events per lane}
        per_chip_h = self.cost["per_chip_hosts"]
        shards = self.cost.get("shards", 1)
        passes = {k: int(n) for k, (_, n) in mix.items()}
        total_passes = sum(passes.values())
        est_pass_bytes = {}
        est_total = 0
        for label, (k, n) in mix.items():
            b = B.get(label, 1)
            if label == "dense":
                pb = 2 * per_chip_h * rb
            elif label.startswith("w"):
                # window-rung: gather/scatter amortized over the whole
                # window; each counted pass drains b batched sub-steps
                pb = 2 * b * k * rb
            else:
                pb = (4 + 2 * b) * k * rb
            est_pass_bytes[label] = pb
            est_total += pb * int(n)
        warm = self.cost.get("warm_wall")
        wall = warm if warm else self.wall_seconds
        # None on a device the memscope table does not list: the
        # roofline fractions are then None ("not measured")
        peak = self.cost.get("hbm_peak_gbps")
        # sharded pass counters sum every chip's passes (shards move
        # their pass bytes CONCURRENTLY), so the per-chip bandwidth —
        # the number comparable to one chip's HBM peak — divides the
        # aggregate by the shard count
        gbps_modeled = est_total / shards / wall / 1e9 if wall else 0.0
        # MEASURED traffic (obs.memscope, PR 15): XLA's own
        # bytes-accessed for the compiled chunk program x chunk calls
        # replaces the hand model when the backend provides it —
        # modeled and measured report side by side, and the headline
        # roofline_frac prefers the measured figure. Like the modeled
        # path, the sharded program's analysis covers all shards'
        # concurrent traffic, so the per-chip figure divides by the
        # shard count.
        xla = (self.memory or {}).get("xla") or {}
        chunks = self.cost.get("chunks")
        meas_total = (xla["bytes_accessed"] * chunks
                      if xla.get("bytes_accessed") and chunks else None)
        gbps_meas = (meas_total / shards / wall / 1e9
                     if meas_total and wall else None)
        gbps = gbps_meas if gbps_meas is not None else gbps_modeled
        out = {
            "row_bytes": rb,
            "hot_columns": self.cost.get("hot_columns"),
            "batch": B,
            "shards": shards,
            "passes": passes,
            "passes_total": total_passes,
            "passes_per_window": (total_passes / self.windows
                                  if self.windows else 0.0),
            "est_pass_bytes": est_pass_bytes,
            "est_total_gb": est_total / 1e9,
            "wall_seconds_used": wall,
            # False = single-chunk run: the wall INCLUDES the cold
            # compile and the gbps figures understate accordingly
            "warm": warm is not None,
            # modeled vs measured, side by side; achieved_gbps_est
            # keeps its name for trajectory readers and carries the
            # best available figure (measured when the backend
            # provides bytes-accessed, modeled otherwise — `measured`
            # says which)
            "achieved_gbps_est": gbps,
            "modeled_gbps": gbps_modeled,
            "measured": gbps_meas is not None,
            "hbm_peak_gbps": peak,
            "roofline_frac": gbps / peak if peak else None,
            "roofline_frac_modeled": (gbps_modeled / peak
                                      if peak else None),
        }
        if meas_total is not None:
            out["measured_total_gb"] = meas_total / 1e9
            out["measured_gbps"] = gbps_meas
            out["roofline_frac_measured"] = (gbps_meas / peak
                                             if peak else None)
        # modeled-vs-MEASURED per pass (obs.passcope): when a
        # --passcope run decoded a device pass table, put each pass's
        # measured device-time share beside the byte model's share —
        # the model only prices bytes; the measured column says where
        # the device time actually went, pass by pass
        dev = self.device_phases
        if dev and dev.get("available"):
            ph = dev.get("phases", {})
            table = {}
            for label, pb in est_pass_bytes.items():
                mb = pb * passes.get(label, 0)
                table[label] = {
                    "modeled_bytes": mb,
                    "modeled_frac": (round(mb / est_total, 4)
                                     if est_total else 0.0),
                }
            for label, rec in ph.items():
                table.setdefault(label, {})
                table[label]["measured_ms"] = rec["ms"]
                table[label]["measured_frac"] = rec["frac"]
            # drain rungs measure against the rung byte rows directly
            for label, rec in dev.get("rungs", {}).items():
                if label in table:
                    table[label]["measured_ms"] = rec["ms"]
                    table[label]["measured_frac"] = rec["frac"]
            out["pass_table"] = table
            out["device_attributed_frac"] = dev.get("attributed_frac")
        return out

    def summary(self) -> dict:
        """The run's headline figures. When the metrics registry is
        enabled (obs.metrics) the dict is also published as ``sim.*``
        gauges, so the CLI, the tracker, bench.py and the metrics.json
        snapshot all read one source of truth."""
        mean_rtt_us = (self.total(defs.ST_RTT_SUM_US) /
                       max(self.total(defs.ST_RTT_COUNT), 1))
        sim_s = self.sim_time_ns / SIMTIME_ONE_SECOND
        s = {
            "hosts": len(self.host_names),
            "events": self.events,
            "windows": self.windows,
            "sim_seconds": sim_s,
            "wall_seconds": self.wall_seconds,
            "events_per_sec": self.events_per_sec,
            "speedup": self.speedup,
            "wall_per_sim_second": (self.wall_seconds / sim_s
                                    if sim_s else 0.0),
            "pkts_sent": self.total(defs.ST_PKTS_SENT),
            "pkts_recv": self.total(defs.ST_PKTS_RECV),
            "drop_net": self.total(defs.ST_PKTS_DROP_NET),
            "drop_buf": self.total(defs.ST_PKTS_DROP_BUF),
            "drop_q": self.total(defs.ST_PKTS_DROP_Q),
            "defer_fanin": self.total(defs.ST_DEFER_FANIN),
            "defer_a2a": self.total(defs.ST_DEFER_A2A),
            "bytes_recv": self.total(defs.ST_BYTES_RECV),
            "retransmits": self.total(defs.ST_RETRANSMIT),
            "sack_reneges": self.total(defs.ST_SACK_RENEGE),
            "transfers_done": self.total(defs.ST_XFER_DONE),
            "transfers_aborted": self.total(defs.ST_TGEN_ABORT),
            "mean_rtt_us": mean_rtt_us,
        }
        # memory observatory figures (obs.memscope): the run's
        # device-buffer watermark and per-host state bytes — the
        # fields bench lines and perf-ledger entries carry
        # (mem_peak_bytes is what tools/perf_regress.py's memory gate
        # compares)
        if self.memory:
            s["mem_peak_bytes"] = int(self.memory.get("peak_bytes", 0))
            s["mem_source"] = self.memory.get("source")
            s["state_bytes_per_host"] = int(
                self.memory.get("state_bytes_per_host", 0))
        # network observatory figures (obs.netscope): exact tail
        # read-outs from the device histograms — the p50/p99 fields
        # ledger entries and bench lines carry so perf trajectories
        # can track tail behavior, not just means
        if self.network:
            kinds = self.network.get("kinds", {})
            s["rtt_p50_us"] = kinds.get("rtt", {}).get("p50_us", 0)
            s["rtt_p99_us"] = kinds.get("rtt", {}).get("p99_us", 0)
            s["completion_p99_s"] = (
                kinds.get("completion", {}).get("p99_us", 0) / 1e6)
        # lockstep-occupancy figures (obs.passcope, always computed
        # from the drain's own pass counters): the waste fraction and
        # the dominating device pass — what bench lines and
        # perf-ledger entries carry for the occupancy regression gate
        # (tools/perf_regress.py)
        if self.occupancy:
            s["waste_frac"] = self.occupancy.get("waste_frac")
            s["lane_utilization"] = self.occupancy.get("utilization")
            from ..obs import passcope as _PC
            lbl, frac = _PC.top_pass(self.device_phases)
            if lbl is not None:
                s["top_pass"] = lbl
                s["top_pass_frac"] = frac
        # robustness figures appear only when the features were used —
        # keeps the BENCH-diffable section stable for plain runs
        if self.faults:
            s["faults_applied"] = len(self.faults)
        if self.hosted:
            s["hosted_exits"] = len(self.hosted)
            s["hosted_failures"] = sum(
                1 for v in self.hosted.values()
                if not v.get("clean", False))
        from ..obs import metrics as M
        if M.ENABLED:
            M.REGISTRY.publish("sim", s)
        return s


def auto_engine_config(scenario: Scenario, topo: Topology) -> EngineConfig:
    """Size the fixed-capacity buffers from the scenario.

    The binding constraint is packets per lookahead window: a host's NIC
    can emit up to bw_up * min_jump bytes between exchanges, so the
    outbox (per-window emit budget) must cover that or the NIC defers to
    the next window and throughput is artificially capped. Destination
    fan-in gets 2x that budget; event queues must hold the inbound burst
    plus timers/wakes. Capacities are clamped so memory stays bounded at
    large H (beyond the clamp the NIC deferral keeps results exact, just
    reflecting genuine queueing).
    """
    from ..core.constants import TCP_MSS

    H = scenario.total_hosts()
    min_jump = topo.min_latency_ns or DEFAULT_MIN_TIME_JUMP

    bw = 0
    for idx, name, spec in scenario.expand_hosts():
        bw = max(bw, spec.bandwidth_up or 0, spec.bandwidth_down or 0)
    if topo.v_bw_up_bytes.size:
        bw = max(bw, int(topo.v_bw_up_bytes.max()),
                 int(topo.v_bw_down_bytes.max()))
    if bw <= 0:
        bw = 128 * 1024 * 1024

    pkts_per_window = (bw * min_jump) // (TCP_MSS * 10**9) + 1

    def pow2(n, lo, hi):
        v = lo
        while v < n and v < hi:
            v *= 2
        return v

    # Memory/pass-cost budget: the burst-sized caps assume every host
    # can saturate its link simultaneously, which at 100k+ hosts would
    # allocate queue arrays in the GBs and make every lockstep pass
    # scan them. Cap the total slot budget (power-of-two bounds so
    # pow2 cannot overshoot); outbox overflow defers to the next
    # window (exact), and the event queue ALWAYS keeps timer/wake
    # headroom above the inbound budget, whatever the clamp says —
    # inbound bursts beyond incap are genuine queue drops, counted.
    def pow2_floor(n):
        return 1 << max(n, 1).bit_length() - 1

    slot_budget = 1 << 24
    hi_q = max(32, min(1024, pow2_floor(slot_budget // max(H, 1))))
    hi_ob = max(16, min(512, pow2_floor(slot_budget // (4 * max(H, 1)))))

    obcap = pow2(int(pkts_per_window * 5 // 4), 16, hi_ob)
    incap = pow2(2 * obcap, 32, 2 * hi_ob)
    qcap = max(pow2(incap + 32, 32, hi_q), incap + 32)
    return EngineConfig(num_hosts=H, qcap=qcap, scap=16, obcap=obcap,
                        incap=incap, txqcap=16)


class Simulation:
    """Build and run one scenario on the JAX engine."""

    def __init__(self, scenario: Scenario, topology: Topology = None,
                 engine_cfg: EngineConfig = None, seed: int = None):
        tb = TR.begin("sim.build")
        self.scenario = scenario
        seed = scenario.seed if seed is None else seed
        self.seed = seed

        src = topology or scenario.topology_graphml or scenario.topology_path
        self.topo = src if isinstance(src, Topology) else build_topology(src)

        H = scenario.total_hosts()
        self.cfg = engine_cfg or auto_engine_config(scenario, self.topo)
        assert self.cfg.num_hosts == H

        # --- register hosts: DNS, attachment, apps (reference
        # _master_registerHosts -> slave_addNewVirtualHost analogue) ---
        self.dns = DNS()
        names, hints = [], []
        for idx, name, spec in scenario.expand_hosts():
            names.append(name)
            hints.append((spec.ip_hint, spec.geocode_hint, spec.type_hint))
            self.dns.register(idx, name, spec.ip_hint if spec.quantity == 1 else None)
        self.host_names = names

        vertex = attach_hosts(self.topo, hints, seed=seed)

        bw_up = np.zeros(H, dtype=np.int64)
        bw_down = np.zeros(H, dtype=np.int64)
        nic_buf = np.full(H, INTERFACE_BUFFER_SIZE, dtype=np.int64)
        cpu_cost = np.zeros(H, dtype=np.int64)
        cpu_threshold = np.full(H, -1, dtype=np.int64)
        rcvbuf0 = np.full(H, -1, dtype=np.int64)   # -1 = autotune
        sndbuf0 = np.full(H, -1, dtype=np.int64)
        # process slots: the reference's per-host process LIST
        # (shd-configuration.h:36-95, slave_addNewVirtualProcess
        # shd-slave.c:293) — e.g. a Tor host runs tor + tgen together
        PP = max((len(s.processes) for _, _, s in
                  scenario.expand_hosts() if s.processes), default=1)
        PP = max(PP, 1)
        if self.cfg.procs_per_host < PP:
            import dataclasses as _dc
            self.cfg = _dc.replace(self.cfg, procs_per_host=PP)
        PP = self.cfg.procs_per_host
        app_kind = np.zeros((H, PP), dtype=np.int32)
        app_cfg = np.zeros((H, PP, 8), dtype=np.int64)
        start_times = np.zeros((H, PP), dtype=np.int64)
        has_app = np.zeros((H, PP), dtype=bool)
        pcap_on = np.zeros(H, dtype=bool)

        from ..apps.tgen import TgenTables
        tgen_tables = TgenTables()
        hosted_specs = []
        for idx, name, spec in scenario.expand_hosts():
            v = vertex[idx]
            bw_up[idx] = spec.bandwidth_up or self.topo.v_bw_up_bytes[v] or 1 << 40
            bw_down[idx] = spec.bandwidth_down or self.topo.v_bw_down_bytes[v] or 1 << 40
            if spec.interface_buffer:
                nic_buf[idx] = spec.interface_buffer
            if spec.socket_recv_buffer:
                rcvbuf0[idx] = spec.socket_recv_buffer
            if spec.socket_send_buffer:
                sndbuf0[idx] = spec.socket_send_buffer
            pcap_on[idx] = spec.pcap
            if spec.cpu_frequency:
                # reference semantics (shd-cpu.c:16-44): cost scales by
                # rawFrequency / hostFrequency; precision-round here at
                # build (the device then only adds a constant).
                ratio = (scenario.cpu_raw_frequency_khz /
                         max(spec.cpu_frequency, 1))
                cost = int(scenario.cpu_event_cost_ns * ratio)
                prec = scenario.cpu_precision_ns
                if prec and prec > 0:
                    cost = ((cost + prec // 2) // prec) * prec
                if cost == 0:
                    import sys as _sys
                    _sys.stderr.write(
                        f"shadow_tpu: warning: host {name!r} sets "
                        f"cpufrequency but its rounded event cost is 0 "
                        f"(precision {prec}ns) — CPU model inactive "
                        "for it\n")
                cpu_cost[idx] = cost
                cpu_threshold[idx] = scenario.cpu_threshold_ns
            for p, proc in enumerate(spec.processes):
                kind, cfg_words = compile_app(proc.plugin, proc.arguments,
                                              self.dns, H,
                                              tgen_tables=tgen_tables)
                app_kind[idx, p] = kind
                app_cfg[idx, p] = cfg_words
                start_times[idx, p] = proc.start_time
                has_app[idx, p] = True
                if proc.plugin.startswith("hosted:"):
                    # a hosted process may share its host with modeled
                    # processes (the reference's canonical tor+tgen
                    # host shape, shd-configuration.h:36-95): the op
                    # replay stamps the hosted slot so sockets wake it
                    # (hosting/bridge.py). One hosted process per host:
                    # the wake-ring records carry no process id, so
                    # two hosted apps on one host would be ambiguous.
                    if any(i == idx for i, _, _, _, _ in hosted_specs):
                        raise NotImplementedError(
                            f"host {name!r} declares two hosted "
                            "processes; at most one per host (modeled "
                            "processes alongside are fine)")
                    hosted_specs.append(
                        (idx, p, name, proc.plugin[len("hosted:"):],
                         proc.arguments))
        # gossip relay draws target uniformly random ids in [0, n);
        # in a mixed scenario any non-gossip id inside that range eats
        # its datagrams silently — validate here, where the whole
        # scenario is visible (apps/compile.py only sees one process).
        from ..apps.base import APP_GOSSIP as _APP_GOSSIP
        gossip_mask = ((app_kind == _APP_GOSSIP) & has_app).any(axis=1)
        if gossip_mask.any():
            gsel = (app_kind == _APP_GOSSIP) & has_app
            n_draw = int(app_cfg[gsel, 0].max())
            bad = int((~gossip_mask[:n_draw]).sum())
            if bad:
                import sys as _sys
                _sys.stderr.write(
                    f"shadow_tpu: warning: gossip peer range n={n_draw} "
                    f"covers {bad} non-gossip host id(s); their relay "
                    "datagrams are silently dropped — pass an explicit "
                    "n= and put the gossip hosts first\n")
        tg_nodes, tg_peers, tg_pool, tg_edges = tgen_tables.arrays()
        if tgen_tables.sync_slots > self.cfg.synccap:
            import dataclasses as _dc
            self.cfg = _dc.replace(self.cfg,
                                   synccap=tgen_tables.sync_slots)

        # Dead-branch pruning (see EngineConfig): record which app kinds
        # exist and whether TCP can be opened at all.
        if self.cfg.app_kinds is None:
            import dataclasses as _dc
            from ..apps.base import (APP_TGEN, APP_BULK, APP_BULK_SERVER,
                                     APP_HOSTED, APP_SOCKS_CLIENT,
                                     APP_SOCKS_PROXY)
            kinds = tuple(sorted(set(
                int(k) for k in app_kind.reshape(-1).tolist())))
            tcp_kinds = {APP_TGEN, APP_BULK, APP_BULK_SERVER, APP_HOSTED,
                         APP_SOCKS_CLIENT, APP_SOCKS_PROXY}
            self.cfg = _dc.replace(
                self.cfg, app_kinds=kinds,
                uses_tcp=bool(tcp_kinds & set(kinds)))

        # CPU-hosted apps (hosting/): real app code bridged per window
        self.hosting = None
        if hosted_specs:
            from ..hosting.api import lookup
            from ..hosting.runtime import HostingRuntime
            apps = {idx: lookup(app_name)(args)
                    for idx, _, _, app_name, args in hosted_specs}
            hnames = {idx: hname for idx, _, hname, _, _ in hosted_specs}
            procs = {idx: p for idx, p, _, _, _ in hosted_specs}
            # zero-arg factories so a fault-injection restart
            # (engine.faults host_up) can respawn a FRESH instance
            factories = {
                idx: (lambda an=app_name, ar=args: lookup(an)(ar))
                for idx, _, _, app_name, args in hosted_specs}
            self.hosting = HostingRuntime(apps, hnames, self.dns, seed,
                                          procs=procs,
                                          factories=factories)
            if self.cfg.scap > 256:
                # hosting packs socket slots into 8-bit handle fields
                # (hosting/bridge.py op_pipe_open) — larger tables
                # would silently alias pipe halves
                raise ValueError(
                    f"hosted apps require scap <= 256 (got "
                    f"{self.cfg.scap}): pipe handles pack the slot "
                    "into 8 bits")
            if self.cfg.hostedcap < 32:
                # concurrent wakes within one window (e.g. several
                # accepts) must all fit the ring or callbacks are lost
                import dataclasses as _dc
                self.cfg = _dc.replace(self.cfg, hostedcap=32)

        # --- fault schedule (engine.faults): compiled at build so bad
        # configs fail here, executed by the run loop at exact sim
        # times (deterministic; dual same-seed runs bit-identical) ---
        self.injector = None
        if scenario.faults:
            from .faults import FaultInjector, compile_faults
            name_to_idx = {name: idx
                           for idx, name, _ in scenario.expand_hosts()}
            events = compile_faults(scenario.faults, name_to_idx, vertex,
                                    topo=self.topo,
                                    stop_time=scenario.stop_time)
            procs_of_host = {
                int(h): [int(p) for p in np.flatnonzero(has_app[h])]
                for h in range(H) if has_app[h].any()}
            self.injector = FaultInjector(
                events, self.topo.latency_ns, self.topo.reliability,
                vertex, procs_of_host, names)
            self.injector.hosting = self.hosting

        self.hp = HostParams(
            hid=jnp.arange(H, dtype=jnp.int32),
            rng_stream=R.stream_of(seed & 0xFFFFFFFF, R.DOMAIN_HOST,
                                   jnp.arange(H, dtype=jnp.int32)),
            vertex=jnp.asarray(vertex, dtype=jnp.int32),
            bw_up=jnp.asarray(bw_up),
            bw_down=jnp.asarray(bw_down),
            app_kind=jnp.asarray(app_kind),
            app_cfg=jnp.asarray(app_cfg),
            nic_buf=jnp.asarray(nic_buf),
            cpu_cost=jnp.asarray(cpu_cost),
            cpu_threshold=jnp.asarray(cpu_threshold),
            rcvbuf0=jnp.asarray(rcvbuf0),
            sndbuf0=jnp.asarray(sndbuf0),
            pcap_on=jnp.asarray(pcap_on),
        )

        if bool((cpu_cost > 0).any()) and not self.cfg.cpu_model:
            import dataclasses as _dc
            self.cfg = _dc.replace(self.cfg, cpu_model=True)

        # pcap capture needs the trace ring sized for a window chunk;
        # bound the chunk so the ring stays modest (capture implies a
        # drain to the host per chunk anyway)
        if pcap_on.any() and self.cfg.tracecap == 0:
            import dataclasses as _dc
            chunk = min(self.cfg.chunk_windows, 16)
            self.cfg = _dc.replace(
                self.cfg, chunk_windows=chunk,
                tracecap=chunk * (self.cfg.obcap + self.cfg.incap))

        min_jump = self.topo.min_latency_ns or DEFAULT_MIN_TIME_JUMP
        self.sh = make_shared(self.topo.latency_ns, self.topo.reliability,
                              R.root_key(seed), scenario.stop_time, min_jump,
                              seed=seed, cc_kind=self.cfg.cc_kind,
                              tgen_nodes=tg_nodes, tgen_peers=tg_peers,
                              tgen_pool=tg_pool, tgen_edges=tg_edges,
                              host_vertex=vertex,
                              host_bw_up=bw_up, host_bw_down=bw_down)

        # --- initial events: process starts (reference process_schedule;
        # one start event per process slot, in slot order) ---
        hosts = alloc_hosts(self.cfg)
        eq_time = np.array(hosts.eq_time)
        eq_kind = np.array(hosts.eq_kind)
        eq_pkt = np.array(hosts.eq_pkt)
        eq_seq = np.array(hosts.eq_seq)
        eq_ctr = np.array(hosts.eq_ctr)
        for p in range(PP):
            idxs = np.flatnonzero(has_app[:, p])
            eq_time[idxs, p] = start_times[idxs, p]
            eq_kind[idxs, p] = EV_APP
            eq_seq[idxs, p] = eq_ctr[idxs]
            eq_pkt[idxs, p, P.ACK] = WAKE_START
            eq_pkt[idxs, p, P.SEQ] = -1
            eq_pkt[idxs, p, P.SRC] = p      # slotless wake: proc slot
            eq_ctr[idxs] += 1
        self.hosts = hosts.replace(
            eq_time=jnp.asarray(eq_time), eq_kind=jnp.asarray(eq_kind),
            eq_seq=jnp.asarray(eq_seq), eq_pkt=jnp.asarray(eq_pkt),
            eq_ctr=jnp.asarray(eq_ctr),
            eq_next=jnp.asarray(eq_time.min(axis=1)))

        self._ran = False
        TR.end(tb)

    def effective_chunk(self, digest_every: int = 0) -> int:
        """The chunk the window program ACTUALLY compiles for: 1
        under hosted apps (the CPU tier runs between every window),
        shrunk to the digest cadence so records land on exact window
        boundaries. One definition shared by run(), prewarm() and the
        ``--shape-fingerprint`` probe — if they ever disagreed, the
        pre-warm would silently warm a program no worker loads."""
        chunk = 1 if self.hosting else self.cfg.chunk_windows
        if digest_every:
            chunk = min(chunk, digest_every)
        return chunk

    def prewarm(self, mesh=None, digest_every: int = 0) -> dict:
        """Materialize the window-chunk executable this scenario will
        run — disk-load or compile — WITHOUT executing it: the fleet
        pre-warm entry point (serving.prewarm; CLI ``--prewarm``).

        Builds exactly the program run() would build for the same
        knobs: the chunk shrinks to 1 under hosted apps and to the
        digest cadence when `digest_every` > 0 (run() records on
        exact window boundaries), and a `mesh` pre-warms the sharded
        program for that concrete device assignment. Populates the
        process-wide memory tier (core.jitcache) and — when a
        persistent cache is active (``--aot-cache`` /
        ``SHADOW_TPU_AOT_CACHE``) — the disk tier, so a later worker
        process opens warm. Donation happens at execution, never at
        compilation, so this Simulation still runs afterwards.

        Returns {"fingerprint", "chunk", "shards", "cache_scope"}.
        """
        from ..obs.ledger import fingerprint_of

        if mesh is None:
            hosts, cfg, hp, sh = self.hosts, self.cfg, self.hp, self.sh
            chunk = self.effective_chunk(digest_every)
            from .window import run_windows_aot
            fn = run_windows_aot(cfg, chunk)
            t0 = jnp.min(hosts.eq_next)
        else:
            from ..parallel.shard import (AXIS, device_put_sharded,
                                          run_windows_sharded_aot)
            n = mesh.shape[AXIS]
            hosts, hp, sh, cfg = self._pad_for_mesh(n)
            hosts, hp, sh = device_put_sharded(hosts, hp, sh, mesh)
            chunk = self.effective_chunk(digest_every)
            fn = run_windows_sharded_aot(cfg, chunk, mesh)
            t0 = jax.jit(jnp.min)(hosts.eq_next)
        wend = jnp.where(t0 == SIMTIME_MAX, t0, t0 + sh.min_jump)
        fn.warm(hosts, hp, sh, t0, wend)
        return {"fingerprint": fingerprint_of(cfg), "chunk": chunk,
                "shards": 1 if mesh is None else mesh.size,
                "cache_scope": fn.cache_scope}

    def _pad_for_mesh(self, n_shards: int):
        """Pad the host dimension to a multiple of the shard count with
        inert hosts (empty queues, no app). Inert rows never emit or
        receive, so stats[:H] are bit-identical to the unpadded run."""
        import dataclasses as _dc

        H = self.cfg.num_hosts
        Hp = ((H + n_shards - 1) // n_shards) * n_shards
        if Hp == H:
            return self.hosts, self.hp, self.sh, self.cfg
        cfg = _dc.replace(self.cfg, num_hosts=Hp)
        fresh = alloc_hosts(cfg)
        hosts = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[H:]], axis=0),
            self.hosts, fresh)
        pad = Hp - H
        hp = HostParams(
            hid=jnp.concatenate([self.hp.hid,
                                 jnp.arange(H, Hp, dtype=jnp.int32)]),
            rng_stream=jnp.concatenate([self.hp.rng_stream,
                                        jnp.zeros(pad, jnp.uint32)]),
            vertex=jnp.concatenate([self.hp.vertex,
                                    jnp.zeros(pad, jnp.int32)]),
            bw_up=jnp.concatenate([self.hp.bw_up,
                                   jnp.ones(pad, jnp.int64)]),
            bw_down=jnp.concatenate([self.hp.bw_down,
                                     jnp.ones(pad, jnp.int64)]),
            app_kind=jnp.concatenate([
                self.hp.app_kind,
                jnp.zeros((pad,) + self.hp.app_kind.shape[1:],
                          jnp.int32)]),
            app_cfg=jnp.concatenate([
                self.hp.app_cfg,
                jnp.zeros((pad,) + self.hp.app_cfg.shape[1:],
                          jnp.int64)]),
            nic_buf=jnp.concatenate([self.hp.nic_buf,
                                     jnp.ones(pad, jnp.int64)]),
            cpu_cost=jnp.concatenate([self.hp.cpu_cost,
                                      jnp.zeros(pad, jnp.int64)]),
            cpu_threshold=jnp.concatenate([self.hp.cpu_threshold,
                                           jnp.full((pad,), -1,
                                                    jnp.int64)]),
            rcvbuf0=jnp.concatenate([self.hp.rcvbuf0,
                                     jnp.full((pad,), -1, jnp.int64)]),
            sndbuf0=jnp.concatenate([self.hp.sndbuf0,
                                     jnp.full((pad,), -1, jnp.int64)]),
            pcap_on=jnp.concatenate([self.hp.pcap_on,
                                     jnp.zeros(pad, jnp.bool_)]),
        )
        sh = self.sh.replace(
            host_vertex=jnp.concatenate(
                [self.sh.host_vertex, jnp.zeros(pad, jnp.int32)]),
            host_bw_up=jnp.concatenate(
                [self.sh.host_bw_up, jnp.ones(pad, jnp.int64)]),
            host_bw_down=jnp.concatenate(
                [self.sh.host_bw_down, jnp.ones(pad, jnp.int64)]))
        return hosts, hp, sh, cfg

    def run(self, verbose: bool = False, mesh=None, heartbeat_s: float = 0,
            logger=None, checkpoint_path: str = None,
            checkpoint_every_s: float = 0, checkpoint_keep: int = 0,
            resume_from: str = None, pcap_dir: str = None,
            trace: str = None, metrics: str = None,
            digest: str = None, digest_every: int = 0,
            digest_context: dict = None, digest_rewind: bool = True,
            resume_unchecked: bool = False,
            netscope: str = None, passcope: str = None) -> SimReport:
        """Run to the stop time. With `mesh` (a 1-D jax Mesh over a
        "hosts" axis) the window program runs under shard_map with the
        host dimension block-sharded — same results, N chips.
        `heartbeat_s` > 0 emits tracker heartbeats on that sim-time
        interval (obs.tracker). `checkpoint_path` + `checkpoint_every_s`
        snapshot state periodically into a crash-safe rotating store
        (engine.checkpoint.CheckpointStore: atomic tmp+fsync+rename
        writes, content hashes, the last `checkpoint_keep` snapshots —
        default 3 — and a ``latest`` pointer); `resume_from` restores
        a snapshot (a concrete .npz, or the store base to resolve the
        newest valid one with corrupt-head fallback). Resume covers
        fault schedules (the snapshot stamps the injector's schedule
        position and link-episode bookkeeping is replayed) and hosted
        apps (checkpointed runs journal each child's shim protocol
        stream; resume respawns children and fast-forwards them by
        deterministic replay — docs/durability.md).

        `netscope` streams the network observatory's per-chunk
        time-series (obs.netscope: stat totals/deltas, active
        connections, histogram deltas) as JSON lines to that path —
        requires ``EngineConfig.netscope`` (the device histograms are
        allocated at Simulation construction). With the knob on and no
        path, records are kept in memory only; either way
        ``SimReport.network`` carries the exact percentile read-outs
        and, with metrics enabled, ``net.*`` gauges are published.

        `passcope` profiles the first few chunks with jax.profiler
        into that directory and decodes the xplane dump into a
        per-pass DEVICE-time table (obs.passcope: jax.named_scope
        labels on every jitted pass, names matching the stateflow
        entries) — ``SimReport.device_phases``; unset, the
        ``SHADOW_TPU_PASSCOPE`` env var enables it the same way.
        Lockstep-occupancy telemetry (``SimReport.occupancy``:
        utilization/waste from the drain's own pass accounting) is
        always on — it reads data the run already returns. Profiling
        is observation only: a passcope run's digest chain is
        byte-identical to a plain run's, and a refusing backend
        degrades to ``available: False``, never a crash.

        `trace` writes a Chrome trace-event JSON timeline (obs.trace:
        per-chunk spans with sim-time args, compile/hosting/tracker/
        pcap/checkpoint spans). `metrics` writes a final metrics.json
        snapshot (obs.metrics) plus per-chunk JSON lines at
        ``<metrics>.chunks.jsonl``.

        `digest` appends a determinism digest chain (obs.digest: one
        JSON line of per-section state hashes every `digest_every`
        windows — default obs.digest.DEFAULT_EVERY — plus at every
        fault boundary and at the end of the run) and writes a
        companion ``<digest>.manifest.json``; diff two chains with
        ``tools/divergence.py``. `digest_context` folds caller context
        (CLI argv, config path) into the manifest. Cadences below the
        chunk size shrink
        the effective chunk so records land on exact window
        boundaries. `resume_unchecked` downgrades the checkpoint
        fingerprint check on `resume_from` to a warning (divergence
        bisection replays under a clamped stop time). On
        `resume_from`, `digest` is by default treated as the crashed
        attempt's own chain file and rewound to the snapshot's stamped
        position; pass `digest_rewind=False` when the chain is a FRESH
        file recording the resumed tail only (divergence replays).

        Trace, metrics and digest install their process-global
        recorders for the duration of this run only; with all unset
        the chunk loop pays a few boolean checks per chunk. If a
        recorder is ALREADY installed process-wide (an outer harness
        like bench.py holding one timeline open across runs), the
        path argument is ignored — this run's records flow into the
        existing recorder and a warning says so. Under a multi-process
        mesh every process collects (the per-chunk stats fetch is a
        collective and must run uniformly) but only process 0 writes
        files.
        """
        assert not self._ran, "Simulation objects are single-use"
        self._ran = True
        # a preemption requested before this run started belongs to a
        # previous run in this process (request_preempt is process-
        # wide); a stale flag must not kill this run at its first
        # boundary. The tiny window between a SIGTERM handler firing
        # and this clear is covered by the preemptor's escalation
        # (fleet workers SIGKILL after a grace period).
        _PREEMPT.clear()
        from ..obs import digest as DG
        from ..obs import metrics as MT
        from ..parallel import dist
        own_tr = own_mt = own_dg = False
        if digest is not None:
            if not DG.ENABLED:
                # under a multi-process mesh every process runs the
                # recorder state machine (the per-record state pull is
                # a collective, so cadence must agree everywhere) but
                # only process 0 writes the chain/manifest files
                DG.install(digest,
                           every=digest_every or DG.DEFAULT_EVERY,
                           context=digest_context,
                           writer=(not dist.is_multiprocess()
                                   or jax.process_index() == 0))
                own_dg = True
            else:
                import sys as _sys
                _sys.stderr.write(
                    "shadow_tpu: warning: a digest recorder is already "
                    "installed process-wide; the path passed to run() "
                    "is ignored and this run's records extend the "
                    "existing chain\n")
        if trace is not None or metrics is not None:
            writer = (not dist.is_multiprocess()
                      or jax.process_index() == 0)
            if trace is not None and not TR.ENABLED:
                TR.install(trace if writer else None)
                own_tr = True
            if metrics is not None and not MT.ENABLED:
                MT.install(metrics if writer else None,
                           jsonl_path=(metrics + ".chunks.jsonl"
                                       if writer else None))
                own_mt = True
            if ((trace is not None and not own_tr) or
                    (metrics is not None and not own_mt)):
                import sys as _sys
                _sys.stderr.write(
                    "shadow_tpu: warning: a trace/metrics recorder is "
                    "already installed process-wide; the path passed "
                    "to run() is ignored and this run's records flow "
                    "into the existing recorder\n")
        try:
            return self._run_impl(
                verbose=verbose, mesh=mesh, heartbeat_s=heartbeat_s,
                logger=logger, checkpoint_path=checkpoint_path,
                checkpoint_every_s=checkpoint_every_s,
                checkpoint_keep=checkpoint_keep,
                resume_from=resume_from, pcap_dir=pcap_dir,
                resume_unchecked=resume_unchecked,
                digest_rewind=digest_rewind, netscope=netscope,
                passcope=passcope)
        finally:
            if TR.ENABLED:
                # the run's tags end with it: later spans (the next
                # Simulation's build) belong to no run
                TR.TRACER.tags = {}
            if own_tr:
                TR.finish()
            if own_mt:
                MT.finish()
            if own_dg:
                DG.finish()

    def _run_impl(self, verbose, mesh, heartbeat_s, logger,
                  checkpoint_path, checkpoint_every_s, resume_from,
                  pcap_dir, resume_unchecked=False,
                  checkpoint_keep=0, digest_rewind=True,
                  netscope=None, passcope=None) -> SimReport:
        from ..obs import digest as DG
        from ..obs import metrics as MT
        from ..obs import passcope as PC
        # hot-loop observability guard: with --trace/--metrics off the
        # per-chunk cost of the whole obs layer is this one boolean
        obs_on = TR.ENABLED or MT.ENABLED
        dg = DG.RECORDER if DG.ENABLED else None
        if TR.ENABLED:
            TR.TRACER.tags = {"run": next(_RUN_SEQ), "chunk": 0}
            _s_tok = TR.TRACER.begin("run.setup")
        H = self.cfg.num_hosts

        from ..parallel import dist
        multiproc = dist.is_multiprocess()
        if multiproc:
            if self.hosting:
                raise NotImplementedError(
                    "hosted apps + multi-process mesh not supported")
            if self.injector is not None:
                raise NotImplementedError(
                    "fault injection + multi-process mesh not "
                    "supported (host-fault surgery needs addressable "
                    "state)")
            # digest recording, checkpoint/resume and pcap ARE
            # supported on a multi-process mesh — including resume +
            # digest (the last PR 5 gate, lifted): every process reads
            # the chain file in DigestRecorder.rewind to refold the
            # kept prefix and re-arm the cadence in lockstep (the
            # per-record state pull is a collective, so all processes
            # must agree when a record is due), while only process 0 —
            # the writer — truncates and later appends; the
            # truncation is an atomic os.replace, so a peer reading
            # concurrently sees a file whose first n records are the
            # kept prefix either way. Each allgathers the relevant
            # state per record/chunk (the documented DCN-hop price of
            # these debug/durability paths); every process must be
            # able to read the snapshot AND chain paths on resume
            # (shared storage).

        tracker = None
        if heartbeat_s:
            from ..obs.tracker import Tracker
            tracker = Tracker(int(heartbeat_s * 10**9), self.host_names,
                              logger)

        from ..obs import netscope as NSC
        nsrec = None
        if self.cfg.netscope:
            # with the knob on, records always accumulate in memory
            # (SimReport.network reads them); the path adds the JSONL
            # stream. Under a multi-process mesh every process samples
            # (the hist pull is a collective) but only process 0 writes.
            nsrec = NSC.NetScope(
                netscope, writer=(not multiproc
                                  or jax.process_index() == 0))
        elif netscope:
            raise ValueError(
                "run(netscope=...) requires EngineConfig.netscope=True "
                "(the device histograms are allocated at Simulation "
                "construction)")

        # pass-time observatory (obs.passcope): jax.profiler around
        # the first few chunks, decoded into a per-pass device-time
        # table keyed by the named_scope labels the window program
        # carries (= the stateflow entry names). Observation only —
        # the compiled program and the digest chain are untouched.
        # Under a multi-process mesh only process 0 traces.
        import os as _os
        pc_dir = (passcope if passcope is not None
                  else _os.environ.get("SHADOW_TPU_PASSCOPE"))
        if pc_dir == "":
            pc_dir = "passcope_trace"
        pscope = None
        if pc_dir and (not multiproc or jax.process_index() == 0):
            pscope = PC.Capture(pc_dir)

        pcap = None
        pcap_on_run = bool(self.cfg.tracecap) and pcap_dir is not None
        if pcap_on_run and (not multiproc or jax.process_index() == 0):
            # under a multi-process mesh only process 0 writes files;
            # the drain below allgathers the rings to it
            from ..obs.pcap import PcapWriter
            traced = np.flatnonzero(np.asarray(self.hp.pcap_on))
            pcap = PcapWriter(pcap_dir, self.host_names,
                              self.dns.ip_array(H), traced)

        from . import checkpoint as ckpt
        fingerprint = ckpt.scenario_fingerprint(self.scenario, self.cfg,
                                                self.seed)
        store = None
        if checkpoint_path:
            store = ckpt.CheckpointStore(checkpoint_path,
                                         keep=checkpoint_keep)
            if self.hosting is not None:
                # checkpointed hosted runs journal every child's shim
                # protocol stream so resume can fast-forward respawned
                # children by deterministic replay (must be armed
                # before any child spawns)
                self.hosting.enable_journal()
        # durability-test crash triggers (SHADOW_TPU_CRASH_SIM_NS /
        # _WALL_S / _GUARD): SIGKILL this process mid-run, exactly a
        # preemption — tests/test_until_complete.py proves the
        # supervised resume is byte-identical
        from .faults import CrashHook
        crash = CrashHook.from_env()

        if dg is not None:
            # run manifest (seed, fingerprint, engine shape, versions,
            # platform, git rev): what makes two chains comparable and
            # a divergence bisect replayable (tools/divergence.py)
            dg.write_manifest(DG.build_manifest(
                self.scenario, self.cfg, self.seed, self.sh,
                self.host_names, dg,
                checkpoint_path=checkpoint_path,
                shards=(1 if mesh is None else mesh.size),
                pcap=pcap_dir is not None,
                faults=self.injector is not None,
                hosted=self.hosting is not None))

        if mesh is None:
            hosts, cfg, hp, sh = self.hosts, self.cfg, self.hp, self.sh
            # hosted chunk-1 + digest-cadence shrink: the one
            # shared definition (a digest run is its own AOT entry,
            # plain runs are untouched)
            chunk = self.effective_chunk(dg.every if dg else 0)
            per_chip_h = cfg.num_hosts

            def step(hosts, sh_seg, ws, we):
                return run_windows(hosts, hp, sh_seg, ws, we, cfg, chunk)
        else:
            from ..parallel.shard import (AXIS, device_put_sharded,
                                          run_windows_sharded)
            n = mesh.shape[AXIS]
            hosts, hp, sh, cfg = self._pad_for_mesh(n)
            hosts, hp, sh = device_put_sharded(hosts, hp, sh, mesh)
            per_chip_h = cfg.num_hosts // n
            # hosted + mesh: the wake rings are per-host rows, so they
            # shard with the rest of the state; the drain loop's ring-
            # overflow pause is shard-local (each shard pauses its own
            # drain), and the CPU tier reads/writes the global arrays
            # between chunks (single-process mesh only — the multiproc
            # gate above still applies). chunk=1: hosted apps need the
            # CPU between every window.
            chunk = self.effective_chunk(dg.every if dg else 0)

            def step(hosts, sh_seg, ws, we):
                return run_windows_sharded(hosts, hp, sh_seg, ws, we,
                                           cfg, chunk, mesh)

        # the REAL stop time, a loop constant: with a fault schedule
        # the per-segment device stop_time is clamped to the next
        # fault (sh_seg below), so every host-side comparison must use
        # this, not the segment scalar
        stop_ns = int(sh.stop_time)
        inj = self.injector

        def dg_record(kind, window, sim_ns):
            # one digest-chain sample (obs.digest): the state pull and
            # the hash are the whole cadence cost, accounted as spans
            # (digest.record around the recorder's digest.pull and
            # digest.hash) + metrics
            if TR.ENABLED:
                _d_tok = TR.TRACER.begin("digest.record", window=window,
                                         kind=kind)
            pulled = hosts
            if multiproc:
                # materialize the GLOBAL state on every process (the
                # collective must run on all of them — which is why
                # the recorder's cadence state machine runs
                # everywhere); only process 0 writes the record
                from jax.experimental import multihost_utils
                pulled = multihost_utils.process_allgather(hosts,
                                                           tiled=True)
            hosted = (self.hosting.digest_state()
                      if self.hosting is not None else None)
            dg.record(pulled, H, window, sim_ns, kind, hosted=hosted)
            if TR.ENABLED:
                TR.TRACER.end(_d_tok)
            if MT.ENABLED:
                reg = MT.REGISTRY
                reg.counter("digest.records").inc()
                reg.gauge("digest.last_window").set(window)
                reg.gauge("digest.bytes_hashed").set(dg.bytes_hashed)

        # cost-model bookkeeping (SimReport.cost_model): pass mix per
        # compaction rung + per-row state bytes
        from .window import pass_batches, pass_labels
        _pl = pass_labels(cfg, per_chip_h)
        _pass_labels = [lbl for lbl, _ in _pl]
        _pass_sizes = [size for _, size in _pl]
        pass_acc = np.zeros(len(_pass_labels), np.int64)
        # lockstep occupancy (obs.passcope): lane utilization from the
        # SAME pass accounting — pure host arithmetic over the rung
        # counts the drain already returns, so it is always on
        _batch = pass_batches(cfg, per_chip_h)

        def occ_now(events):
            return PC.occupancy(
                {lbl: (size, int(nn)) for lbl, size, nn
                 in zip(_pass_labels, _pass_sizes, pass_acc)},
                events, _batch)
        # shard-imbalance accounting (VERDICT r5 missing #4 — the
        # prerequisite for load-aware placement): the sharded window
        # program returns a PER-SHARD rung mix, and per chunk one
        # jitted reduction yields per-shard cumulative events +
        # currently-active host counts (multiproc-safe: replicated
        # outputs, the eager-t0 pattern above). Published as shard.*
        # gauges -> the metrics.json `shards` section.
        n_shards = 1 if mesh is None else cfg.num_hosts // per_chip_h
        shard_pass_acc = (np.zeros((n_shards, len(_pass_labels)),
                                   np.int64) if n_shards > 1 else None)
        _shard_load = None
        # per-shard load is a SINGLE-process feature (like the [S,NR]
        # pass mix): on a multi-process mesh the reduction's [S]
        # output inherits the host axis's sharding, so each process
        # could not np.asarray it (non-addressable shards)
        if MT.ENABLED and n_shards > 1 and jax.process_count() == 1:
            _shard_load = jax.jit(lambda st, eqn: (
                jnp.sum(st[:, defs.ST_EVENTS].reshape(n_shards, -1),
                        axis=1),
                jnp.sum((eqn < SIMTIME_MAX).reshape(n_shards, -1),
                        axis=1, dtype=jnp.int32)))
        # per-pass traffic covers the drain's HOT working set only:
        # the hot/cold split (state.hot_fields) keeps cold columns out
        # of every rung gather/scatter and loop carry, so modeling
        # them in the pass cost would overstate HBM traffic — on the
        # UDP tiers by more than half the socket table
        _hot = hot_fields(cfg)
        row_bytes = sum(
            int(np.prod(getattr(hosts, f).shape[1:]))
            * getattr(hosts, f).dtype.itemsize
            for f in _hot)

        # memory observatory (obs.memscope): per-chunk device-buffer
        # high-water sampling — real device memory stats where the
        # backend provides them (per device, so a mesh run's
        # per_device list IS the per-shard watermark), RSS fallback on
        # CPU. Host-side reads only, so a memscope-enabled run's
        # digest chain is byte-identical to a plain run's.
        from ..obs import memscope as MS
        if mesh is None:
            wm = MS.Watermark()
        else:
            from ..parallel.shard import mesh_local_devices
            wm = MS.Watermark(mesh_local_devices(mesh))

        if multiproc:
            # eager reductions cannot run on non-addressable global
            # arrays; a jitted min yields a replicated (addressable)
            # scalar on every process
            t0 = jax.jit(jnp.min)(hosts.eq_next)
        else:
            t0 = jnp.min(hosts.eq_next)
        wstart = t0
        wend = jnp.where(t0 == SIMTIME_MAX, t0, t0 + sh.min_jump)

        total_windows = 0
        if resume_from:
            snap = ckpt.load(resume_from, hosts, fingerprint,
                             strict=not resume_unchecked)
            hosts = snap.hosts
            wstart = jnp.int64(snap.wstart)
            wend = jnp.int64(snap.wend)
            total_windows = snap.windows
            if mesh is not None:
                # hp/sh are already placed; only the restored Hosts
                # arrays need (re-)sharding
                from ..parallel.shard import put_hosts
                hosts = put_hosts(hosts, mesh)
            if inj is not None:
                # the schedule is a pure function of the config, so
                # the snapshot records only the POSITION: fast_forward
                # replays the link-episode bookkeeping (host-fault
                # effects already live in the restored arrays) and
                # rebuilds the Shared lat/rel tables exactly
                if snap.fault_idx < 0:
                    raise ValueError(
                        "snapshot records no fault schedule position "
                        "(__fault_idx__); it was taken by a run "
                        "without this fault config — refusing to "
                        "resume into one")
                sh = inj.fast_forward(snap.fault_idx, sh)
                if mesh is not None:
                    from ..parallel.shard import put_shared
                    sh = put_shared(sh, mesh)
            if self.hosting is not None:
                if snap.hosted_blob is None:
                    raise ValueError(
                        "scenario hosts real processes but the "
                        "snapshot has no hosted sidecar "
                        "(<snapshot>.npz.hosted) — it was taken "
                        "without hosted-app support")
                # rebuild the hosted tier and fast-forward respawned
                # children by journal replay (hosting.runtime.restore)
                self.hosting.restore(snap.hosted_blob)

        if dg is not None:
            # the cadence clock is per-run: a recorder spanning
            # several runs (outer harness) or a resume jump must not
            # inherit the previous run's next_due. A resumed run
            # REWINDS the chain the crashed attempt left to exactly
            # the position the snapshot stamped: the kept prefix is
            # identical to a fresh run's (determinism), later records
            # are re-produced live, so the final chain is
            # byte-identical to an uninterrupted run's. A divergence
            # replay resumes the SIMULATION from a snapshot but
            # records a fresh chain of the tail only — it opts out
            # via digest_rewind=False (the snapshot's stamped count
            # belongs to the original run's chain, not this file)
            if (resume_from and snap.digest_records >= 0
                    and digest_rewind):
                dg.rewind(snap.digest_records, snap.digest_chain)
                if dg.due(total_windows):
                    # the crashed attempt died between this snapshot
                    # and the cadence record due at the very same
                    # boundary — emit it now from the restored state,
                    # exactly where the uninterrupted run did
                    dg_record("cadence", total_windows,
                              min(int(wstart), stop_ns))
            else:
                dg.begin_run(total_windows)

        if checkpoint_path and not checkpoint_every_s:
            raise ValueError(
                "checkpoint_path requires checkpoint_every_s > 0 "
                "(otherwise no snapshot would ever be written)")
        next_ckpt = (int(checkpoint_every_s * 10**9)
                     if checkpoint_every_s else 0)
        ckpt_at = int(wstart) + next_ckpt if next_ckpt else None

        # fleet liveness heartbeat (docs/fleet.md): checkpoints and
        # digests are SIM-paced, so on a slow box a healthy run can
        # legitimately write nothing for a long wall time — the fleet
        # watchdog needs a WALL-paced progress signal. Under a fleet
        # worker (SHADOW_TPU_FLEET_RUN_DIR) the loop touches
        # <run_dir>/heartbeat once per chunk; one tiny write per
        # device dispatch, nothing off the fleet path.
        import os as _os
        _hb_dir = _os.environ.get("SHADOW_TPU_FLEET_RUN_DIR")
        _hb_path = (_os.path.join(_hb_dir, "heartbeat")
                    if _hb_dir else None)

        def heartbeat(ws_now):
            if _hb_path is None:
                return
            try:
                with open(_hb_path, "w") as f:
                    f.write(f"{ws_now}\n")
            except OSError:
                pass           # liveness is best-effort, never fatal

        def save_snapshot(ws_now):
            # one snapshot at the current chunk boundary — the cadence
            # path and the cooperative-preemption path share it. Stamps
            # the injector's schedule position and the digest chain
            # position (record count + running hash): resume re-arms
            # both exactly, so records and fault applications landing
            # AFTER this save in the same loop iteration are
            # re-produced live, never duplicated or lost.
            to_save = hosts
            if multiproc:
                # materialize the GLOBAL state on every process (the
                # collective must run on all of them), then only
                # process 0 touches the filesystem
                from jax.experimental import multihost_utils
                to_save = multihost_utils.process_allgather(
                    hosts, tiled=True)
            if not multiproc or jax.process_index() == 0:
                store.save(
                    to_save, ws_now, int(wend), total_windows,
                    fingerprint,
                    fault_idx=(inj.i if inj is not None else -1),
                    digest_records=(len(dg.records)
                                    if dg is not None else -1),
                    digest_chain=(dg.chain_hex
                                  if dg is not None else None),
                    hosted_blob=(self.hosting.snapshot()
                                 if self.hosting is not None
                                 else None))
        if TR.ENABLED:
            # everything up to here: topology/mesh placement, writers,
            # checkpoint fingerprint/restore — the pre-loop cost
            TR.TRACER.end(_s_tok)
        # the passcope trace arms at the FIRST chunk_done(), after the
        # cold compile — tracing a multi-minute XLA compile is both
        # ruinously slow and useless to the pass table; the HLO
        # metadata plane is emitted at execution time so a post-compile
        # trace still decodes fully (obs.passcope.Capture)
        wall0 = _time.perf_counter()
        first_chunk_wall = None
        chunk_i = 0
        n_chunks = 0     # unconditional (chunk_i only counts with obs
        #   on): the cost model scales the window program's measured
        #   bytes-accessed by how many times the chunk executed
        # resumed runs restore pre-checkpoint ST_EVENTS with the state:
        # baseline the per-chunk delta on it or the first chunk's
        # telemetry would claim the whole pre-checkpoint history
        prev_events = (int(_ev_sum(hosts.stats))
                       if obs_on and resume_from else 0)
        while True:
            heartbeat(int(wstart))
            if _PREEMPT.is_set() and not multiproc:
                # cooperative preemption (request_preempt — SIGTERM
                # under --checkpoint, the fleet worker protocol):
                # persist a snapshot at this exact chunk boundary and
                # stop; ``--resume latest`` continues with zero lost
                # work and — digest rewind — a final chain
                # byte-identical to an uninterrupted run's. Checked at
                # the loop top so natural completion always wins (the
                # loop is only re-entered when work remains).
                # Multi-process meshes ignore the flag: signal
                # delivery is per-process and an asymmetric raise
                # would wedge the collectives — preempt those with
                # SIGKILL + periodic snapshots instead.
                saved = False
                if store is not None:
                    if TR.ENABLED:
                        _k_tok = TR.TRACER.begin(
                            "checkpoint.preempt_save")
                    save_snapshot(int(wstart))
                    saved = True
                    if TR.ENABLED:
                        TR.TRACER.end(_k_tok)
                if MT.ENABLED:
                    MT.REGISTRY.counter("engine.preemptions").inc()
                if self.hosting is not None:
                    # children die with this run; resume respawns and
                    # fast-forwards them from the snapshot's journals
                    self.hosting.shutdown()
                raise Preempted(min(int(wstart), stop_ns), saved)
            # fault segmentation (engine.faults): bound this device
            # segment at the next scheduled fault so the engine
            # executes every event strictly before it, stops, and the
            # injector applies the fault at its exact sim time — the
            # stop_time clamp the window program already honors
            # (window.win_body's we_eff), reused as the fault barrier
            sh_seg = sh
            if inj is not None:
                nf = inj.next_time()
                if nf is not None and nf < stop_ns:
                    sh_seg = sh.replace(stop_time=jnp.int64(nf))
                    if mesh is not None:
                        from ..parallel.shard import put_shared
                        sh_seg = put_shared(sh_seg, mesh)
            if obs_on:
                _ws0 = int(wstart)
                _c0 = _time.perf_counter_ns()
            if TR.ENABLED:
                # chunk > [compile+first_chunk >] chunk.dispatch (the
                # step call until it returns) then chunk.wait (until
                # its results are on the host)
                TR.TRACER.tags["chunk"] = n_chunks
                _ck_tok = TR.TRACER.begin("chunk", sim_ns_start=_ws0)
                if first_chunk_wall is None:
                    _cf_tok = TR.TRACER.begin("compile+first_chunk")
                _cd_tok = TR.TRACER.begin("chunk.dispatch")
            hosts, wstart, wend, n, pc = step(hosts, sh_seg, wstart,
                                              wend)
            if TR.ENABLED:
                TR.TRACER.end(_cd_tok)
                _cw_tok = TR.TRACER.begin("chunk.wait")
            total_windows += int(n)
            pc_np = np.asarray(pc)
            if TR.ENABLED:
                TR.TRACER.end(_cw_tok)
            if pc_np.ndim == 2:    # sharded: [n_shards, NR] rung mix
                pass_acc += pc_np.sum(axis=0)
                if shard_pass_acc is not None:
                    shard_pass_acc += pc_np
            else:
                pass_acc += pc_np
            n_chunks += 1
            wm.sample()
            if pscope is not None:
                pscope.chunk_done()   # stops after its chunk budget
            if first_chunk_wall is None:
                # everything after this excludes the cold compile
                first_chunk_wall = _time.perf_counter() - wall0
                if TR.ENABLED:
                    # where the cold XLA build went (the cost model's
                    # "warm" exclusion) — nested inside the first
                    # chunk span so self-times attribute correctly
                    TR.TRACER.end(_cf_tok)
            ws = int(wstart)
            if self.hosting is not None:
                if TR.ENABLED:
                    _h_tok = TR.TRACER.begin("hosting.step")
                now = min(ws, stop_ns)
                hosts = self.hosting.step(hosts, hp, sh, now)
                if mesh is not None:
                    # the op-replay program may hand back differently-
                    # placed arrays; the AOT sharded window program
                    # requires its exact input sharding
                    from ..parallel.shard import put_hosts
                    hosts = put_hosts(hosts, mesh)
                dropped = int(np.asarray(hosts.hw_drop).sum())
                if dropped:
                    raise RuntimeError(
                        f"{dropped} hosted-app wakes lost to wake-ring "
                        "overflow; raise EngineConfig.hostedcap")
                # ops may have queued events earlier than the next
                # window the engine computed — re-derive the window
                # (carried outbox arrivals count, engine.window.
                # next_wakeup)
                nt = jnp.minimum(jnp.min(hosts.eq_next),
                                 jnp.min(hosts.ob_next))
                wstart = nt
                wend = jnp.where(nt == SIMTIME_MAX, nt, nt + sh.min_jump)
                ws = int(wstart)
                if TR.ENABLED:
                    TR.TRACER.end(_h_tok)
            if pcap_on_run:
                if TR.ENABLED:
                    _p_tok = TR.TRACER.begin("pcap.drain")
                # every process participates in the gather (it is a
                # collective); only process 0 holds a writer
                tr_t = dist.gather_stats(hosts.tr_time)
                tr_p = dist.gather_stats(hosts.tr_pkt)
                tr_c = dist.gather_stats(hosts.tr_cnt)
                if pcap is not None:
                    pcap.drain(tr_t, tr_p, tr_c)
                if multiproc:
                    # jitted creation: uniform on all processes, keeps
                    # the sharded placement (the eager-t0 pattern above)
                    hosts = hosts.replace(
                        tr_cnt=_zeros_like(hosts.tr_cnt))
                else:
                    hosts = hosts.replace(
                        tr_cnt=jnp.zeros_like(hosts.tr_cnt))
                if TR.ENABLED:
                    TR.TRACER.end(_p_tok)
            if tracker is not None and tracker.due(min(ws, stop_ns)):
                if TR.ENABLED:
                    _t_tok = TR.TRACER.begin("tracker.heartbeat")
                from ..obs.tracker import socket_columns
                # [socket]/[ram] columns are per-process state; under a
                # multi-process mesh only the stats all-gather exists,
                # so those families are single-process only
                _tst = dist.gather_stats(hosts.stats)[:H]
                tracker.maybe_heartbeat(
                    min(ws, stop_ns), _tst,
                    socks=None if multiproc else socket_columns(hosts),
                    hosted_rss=(self.hosting.child_rss()
                                if self.hosting is not None else None),
                    dev_peak=wm.peak_bytes,
                    waste=occ_now(int(np.asarray(_tst)
                                      [:, defs.ST_EVENTS].sum())
                                  )["waste_frac"])
                if TR.ENABLED:
                    TR.TRACER.end(_t_tok)
            if nsrec is not None:
                # network time-series sample: one record per chunk,
                # derived from device state + sim time only (dual-run
                # byte-identity). The hist/stats pulls are collectives
                # under a multi-process mesh — must run uniformly;
                # active-conn counting reads per-process socket state,
                # so it is single-process only (like [socket] lines)
                if TR.ENABLED:
                    _n_tok = TR.TRACER.begin("netscope.sample")
                nsrec.sample(
                    total_windows, min(ws, stop_ns),
                    np.asarray(dist.gather_stats(hosts.ns_hist))[:H],
                    np.asarray(dist.gather_stats(hosts.stats))[:H],
                    conns=(None if multiproc else
                           int(np.asarray(hosts.sk_used).sum())))
                if TR.ENABLED:
                    TR.TRACER.end(_n_tok)
            if checkpoint_path and ckpt_at is not None and ws >= ckpt_at:
                if TR.ENABLED:
                    _k_tok = TR.TRACER.begin("checkpoint.save")
                save_snapshot(ws)
                ckpt_at += next_ckpt
                if TR.ENABLED:
                    TR.TRACER.end(_k_tok)
            if crash is not None:
                # durability-test preemption: lands AFTER the
                # checkpoint block, so a snapshot due at this boundary
                # is durable before the kill
                crash.maybe_fire(ws)
            if obs_on:
                # per-chunk sim<->wall correlation: one jitted scalar
                # reduction per chunk (replicated on every process
                # under a multi-process mesh — must run uniformly; see
                # run() docstring) buys the events-executed annotation
                # on every chunk record
                sim_end = min(ws, stop_ns)
                ev_total = int(_ev_sum(hosts.stats))
                ev = ev_total - prev_events
                prev_events = ev_total
                if TR.ENABLED:
                    TR.TRACER.end(_ck_tok, sim_ns_end=sim_end,
                                  windows=int(n), events=ev)
                if MT.ENABLED:
                    reg = MT.REGISTRY
                    reg.counter("engine.chunks").inc()
                    reg.counter("engine.windows").inc(int(n))
                    reg.counter("engine.events").inc(ev)
                    reg.gauge("engine.sim_ns").set(sim_end)
                    chunk_wall = (_time.perf_counter_ns() - _c0) / 1e9
                    chunk_sim = max(sim_end - _ws0, 0) / 1e9
                    reg.chunk(
                        chunk=chunk_i, sim_ns_start=_ws0,
                        sim_ns_end=sim_end, windows=int(n), events=ev,
                        wall_s=round(chunk_wall, 6),
                        events_per_sec=(round(ev / chunk_wall, 1)
                                        if chunk_wall else None),
                        wall_per_sim_second=(
                            round(chunk_wall / chunk_sim, 6)
                            if chunk_sim else None),
                        # cumulative lane waste so far: the per-chunk
                        # occupancy trend tools/parse_heartbeat.py and
                        # the waste gate read
                        waste_frac=occ_now(ev_total)["waste_frac"])
                    if _shard_load is not None:
                        # per-shard load: cumulative events + hosts
                        # with pending work right now; the imbalance
                        # gauge is max/mean (1.0 = perfectly balanced)
                        ev_s, act_s = _shard_load(hosts.stats,
                                                  hosts.eq_next)
                        ev_s = np.asarray(ev_s)
                        act_s = np.asarray(act_s)
                        for si in range(n_shards):
                            reg.gauge(f"shard.events.{si}").set(
                                int(ev_s[si]))
                            reg.gauge(
                                f"shard.active_hosts.{si}").set(
                                int(act_s[si]))
                        mean_ev = float(ev_s.mean())
                        reg.gauge("shard.imbalance").set(
                            float(ev_s.max()) / mean_ev
                            if mean_ev else 0.0)
                chunk_i += 1
            if dg is not None and dg.due(total_windows):
                dg_record("cadence", total_windows, min(ws, stop_ns))
            if verbose:
                print(f"  t={ws / SIMTIME_ONE_SECOND:.3f}s "
                      f"windows={total_windows}")
            # fault application: the engine drained every event below
            # the segment bound — apply the head fault batch at its
            # own time, then re-derive the window (a kill's RSTs and a
            # restart's start events may open one before the old ws)
            if inj is not None:
                nf = inj.next_time()
                if nf is not None and nf < stop_ns and ws >= nf:
                    if TR.ENABLED:
                        _fi_tok = TR.TRACER.begin("faults.apply")
                    hosts, sh = inj.apply_batch(hosts, sh)
                    if mesh is not None:
                        from ..parallel.shard import (put_hosts,
                                                      put_shared)
                        hosts = put_hosts(hosts, mesh)
                        sh = put_shared(sh, mesh)
                    nt = jnp.minimum(jnp.min(hosts.eq_next),
                                     jnp.min(hosts.ob_next))
                    wstart = nt
                    wend = jnp.where(nt == SIMTIME_MAX, nt,
                                     nt + sh.min_jump)
                    ws = int(wstart)
                    if TR.ENABLED:
                        TR.TRACER.end(_fi_tok)
                    if dg is not None:
                        # fault boundary: sample at the fault's own
                        # sim time — where a broken-determinism hunt
                        # wants the tightest bracketing
                        dg_record("fault", total_windows, int(nf))
            # a pending fault must keep the loop alive even when the
            # engine has nothing left to do (ws hits SIMTIME_MAX once
            # the queues drain, yet a host_up restart re-populates
            # them; one fault batch is consumed per iteration, so this
            # terminates)
            more_faults = (inj is not None and inj.next_time() is not None
                           and inj.next_time() < stop_ns)
            if (ws >= stop_ns or ws >= SIMTIME_MAX) and not more_faults:
                break
        if dg is not None:
            dg_record("final", total_windows,
                      min(stop_ns, ws) if ws < SIMTIME_MAX else stop_ns)
        if pcap is not None:
            pcap.close()
        if TR.ENABLED:
            _f_tok = TR.TRACER.begin("report.finalize")
        stats = dist.gather_stats(hosts.stats)[:H]
        wall = _time.perf_counter() - wall0
        self.final_hosts = hosts
        if self.hosting is not None:
            self.hosting.shutdown()
        peaks = dist.gather_stats(hosts.cap_peaks)[:H].max(axis=0)
        capacity = {"rows": [
            ("event_queue", cfg.qcap, int(peaks[0])),
            ("socket_table", cfg.scap, int(peaks[1])),
            ("outbox", cfg.obcap, int(peaks[2])),
            ("nic_txq", cfg.txqcap, int(peaks[3])),
        ]}
        sim_ns = min(stop_ns, ws) if ws < SIMTIME_MAX else stop_ns
        warm = (wall - first_chunk_wall
                if first_chunk_wall is not None and
                wall > first_chunk_wall * 1.05 else None)
        cost = {
            "row_bytes": row_bytes,
            "hot_columns": len(_hot),
            "pass_mix": {lbl: (size, int(nn)) for lbl, size, nn in
                         zip(_pass_labels, _pass_sizes, pass_acc)},
            "batch": _batch,
            "per_chip_hosts": per_chip_h,
            "shards": (1 if mesh is None else
                       cfg.num_hosts // per_chip_h),
            "warm_wall": warm,
            "chunks": n_chunks,
            # the one HBM-peak definition (obs.memscope), for the
            # device this run's state lives on; None = not in the table
            "hbm_peak_gbps": MS.hbm_peak_gbps(
                next(iter(hosts.stats.devices())).device_kind),
        }
        # memory observatory record (obs.memscope): the final
        # watermark, the state byte census at the as-run shapes, and
        # the window program's captured XLA cost/memory analysis —
        # what SimReport.memory / summary() / the ledger's
        # mem_peak_bytes field and cost_model()'s measured traffic all
        # read
        wm.sample()
        wm_snap = wm.snapshot()
        census = MS.state_census(cfg, hosts=hosts, hp=hp, sh=sh)
        if mesh is None:
            from .window import run_windows_aot
            xla = run_windows_aot(cfg, chunk).analysis
        else:
            from ..parallel.shard import run_windows_sharded_aot
            xla = run_windows_sharded_aot(cfg, chunk, mesh).analysis
        # network observatory report (obs.netscope): exact percentile
        # read-outs from the FINAL device histograms (not the last
        # sample — a zero-chunk run still reports)
        network = {}
        if nsrec is not None:
            network = NSC.report(
                np.asarray(dist.gather_stats(hosts.ns_hist))[:H])
            network["records"] = len(nsrec.records)
            if nsrec.path:
                network["path"] = nsrec.path
            nsrec.close()
        memrec = dict(wm_snap)
        memrec["state_bytes"] = census["bytes"]
        memrec["state_bytes_per_host"] = census["per_host"]
        memrec["hot_state_bytes"] = \
            census["hosts"]["hot"]["runtime_bytes"]
        memrec["cold_state_bytes"] = \
            census["hosts"]["hot"]["runtime_cold_bytes"]
        memrec["sections"] = census["hosts"]["sections"]
        memrec["xla"] = xla
        # lockstep-occupancy read-out (obs.passcope): always on — the
        # pass counts and event totals are already host-side. The
        # per-shard view composes with the shard.imbalance gauges.
        events_total = int(np.asarray(stats)[:, defs.ST_EVENTS].sum())
        occ = occ_now(events_total)
        shards_occ = None
        if (shard_pass_acc is not None and shard_pass_acc.any()
                and not multiproc):
            ev_s = (np.asarray(hosts.stats)[:, defs.ST_EVENTS]
                    .reshape(n_shards, -1).sum(axis=1))
            shards_occ = PC.shard_occupancy(shard_pass_acc, ev_s,
                                            _pl, _batch)
            occ["shards"] = shards_occ
        # device pass table: stop the profiler (if its chunk budget
        # didn't already) and decode the xplane dump
        dev = pscope.result() if pscope is not None else {}
        if pscope is not None:
            # the decoded table lands next to the raw trace so
            # tools/trace_report.py can merge it offline
            import json as _json
            try:
                with open(_os.path.join(pc_dir, "passcope.json"),
                          "w") as f:
                    _json.dump({"device_phases": dev,
                                "occupancy": occ}, f, indent=1,
                               sort_keys=True)
            except OSError:
                pass
        report = SimReport(stats=stats, host_names=self.host_names,
                           sim_time_ns=sim_ns, wall_seconds=wall,
                           windows=total_windows,
                           heartbeats=(tracker.lines if tracker else []),
                           capacity=capacity, cost=cost,
                           memory=memrec, network=network,
                           device_phases=dev, occupancy=occ,
                           hosted=(self.hosting.exit_info()
                                   if self.hosting is not None else {}),
                           faults=(inj.log if inj is not None else []))
        if TR.ENABLED:
            TR.TRACER.end(_f_tok)
        if MT.ENABLED:
            MT.REGISTRY.gauge("engine.first_chunk_wall_s").set(
                first_chunk_wall or 0.0)
            # memory observatory gauges -> the metrics.json `memory`
            # section (watermark + census + captured XLA analysis)
            MS.publish(MT.REGISTRY, watermark=wm_snap, census=census,
                       xla=xla)
            if network:
                # network observatory gauges -> the metrics.json `net`
                # section (per-kind counts, percentiles, buckets)
                NSC.publish(MT.REGISTRY, network)
            # occupancy.* / passcope.* gauges -> the metrics.json
            # `occupancy` and `device_phases` sections
            PC.publish(MT.REGISTRY, occ=occ, dev=dev or None,
                       shards=shards_occ)
            if shard_pass_acc is not None and shard_pass_acc.any():
                # per-shard pass totals + rung mix: which shard went
                # dense while its peers rode the small rungs — the
                # busy-shard signature load-aware placement needs
                # (multi-process meshes return only the reduced
                # total, so the per-shard table stays zero and is
                # not published there)
                reg = MT.REGISTRY
                for si in range(n_shards):
                    reg.gauge(f"shard.passes.{si}").set(
                        int(shard_pass_acc[si].sum()))
                    for lbl, npss in zip(_pass_labels,
                                         shard_pass_acc[si]):
                        if npss:
                            reg.gauge(
                                f"shard.pass_mix.{lbl}.{si}").set(
                                int(npss))
            # summary() publishes itself into the registry (sim.*
            # gauges) — the snapshot's BENCH-diffable section
            report.summary()
            if TR.ENABLED:
                # phase attribution into the snapshot's `perf`
                # section: the registry closes with this run, so a
                # --perf/--metrics combo (where main owns the tracer
                # and only reads it AFTER run returns) still gets the
                # breakdown metrics.json documents. The finalize span
                # just completed above, so the spans cover the run.
                from ..obs import perf as _PF
                _PF.publish(
                    _PF.attribute(TR.TRACER.events, wall,
                                  report.events),
                    MT.REGISTRY)
        return report


def run_scenario(scenario: Scenario, **kw) -> SimReport:
    return Simulation(scenario, **kw).run()
