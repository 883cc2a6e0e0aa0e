"""The plain deployment description -> the reference engine's tables.

The same semantics as the system's scenario build (host registration,
attachment to topology vertices by each host's geocode hint, ties
broken from the seed, app compilation, the all-pairs routes, one start
event per process), built from the description alone: the program's
tables are never read. The code is the frozen copy netref.py
describes. Engine knobs
not in the description keep the simulator's CLI defaults: round-robin
NIC service, cubic congestion control, an initial window of 10
segments, autotuned socket buffers, no CPU model.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from . import wire as W
from .compile import compile_app
from .constants import INTERFACE_BUFFER_SIZE
from .defs import EV_APP, WAKE_START
from .dns import DNS
from .simtime import SIMTIME_MAX
from .tgen import TgenTables
from .topology import attach_hosts, build_topology

QDISC_RR = 1
CC_CUBIC = 2
TCP_INIT_WND = 10.0
TCP_SSTHRESH0 = 0.0


def expand(dep: dict):
    """(index, name, host entry) with the simulator's naming."""
    i = 0
    for h in dep["hosts"]:
        for q in range(h["quantity"]):
            name = h["id"] if h["quantity"] == 1 else f"{h['id']}{q + 1}"
            yield i, name, h
            i += 1


def build(dep: dict):
    hosts = list(expand(dep))
    H = len(hosts)
    caps = dep["caps"]
    PP = max(max(len(h["processes"]) for _, _, h in hosts), 1)
    cfg = SimpleNamespace(
        num_hosts=H, qcap=caps["qcap"], scap=caps["scap"],
        obcap=caps["obcap"], incap=caps["incap"], txqcap=caps["txqcap"],
        qdisc=QDISC_RR, cpu_model=False, procs_per_host=PP)

    topo = build_topology(dep["topology_graphml"])
    dns = DNS()
    for idx, name, h in hosts:
        dns.register(idx, name, None)
    vertex = attach_hosts(topo, [(None, h.get("geocode"), None)
                                 for _, _, h in hosts], seed=dep["seed"])

    bw_up = np.zeros(H, np.int64)
    bw_down = np.zeros(H, np.int64)
    app_kind = np.zeros((H, PP), np.int32)
    app_cfg = np.zeros((H, PP, 8), np.int64)
    start = np.zeros((H, PP), np.int64)
    has_app = np.zeros((H, PP), bool)
    tables = TgenTables()
    for idx, name, h in hosts:
        v = vertex[idx]
        bw_up[idx] = topo.v_bw_up_bytes[v] or 1 << 40
        bw_down[idx] = topo.v_bw_down_bytes[v] or 1 << 40
        for p, proc in enumerate(h["processes"]):
            kind, words = compile_app(proc["plugin"], proc["args"], dns, H,
                                      tgen_tables=tables)
            app_kind[idx, p] = kind
            app_cfg[idx, p] = words
            start[idx, p] = proc["start_ns"]
            has_app[idx, p] = True
    tg_nodes, tg_peers, tg_pool, tg_edges = tables.arrays()

    hp = SimpleNamespace(
        vertex=vertex, bw_up=bw_up, bw_down=bw_down, app_kind=app_kind,
        app_cfg=app_cfg, nic_buf=np.full(H, INTERFACE_BUFFER_SIZE, np.int64),
        sndbuf0=np.full(H, -1, np.int64), rcvbuf0=np.full(H, -1, np.int64))
    sh = SimpleNamespace(
        lat_ns=topo.latency_ns, rel=topo.reliability,
        stop_time=dep["stop_ns"], min_jump=max(dep["runahead_ns"], 1),
        rng_root=None, cc_kind=CC_CUBIC, tcp_init_wnd=TCP_INIT_WND,
        tcp_ssthresh0=TCP_SSTHRESH0, tgen_nodes=tg_nodes,
        tgen_peers=tg_peers, tgen_pool=tg_pool, tgen_edges=tg_edges)

    Q = cfg.qcap
    eq_time = np.full((H, Q), SIMTIME_MAX, np.int64)
    eq_kind = np.zeros((H, Q), np.int32)
    eq_seq = np.zeros((H, Q), np.int32)
    eq_pkt = np.zeros((H, Q, W.PKT_WORDS), np.int32)
    eq_ctr = np.zeros(H, np.int32)
    for p in range(PP):
        idxs = np.flatnonzero(has_app[:, p])
        eq_time[idxs, p] = start[idxs, p]
        eq_kind[idxs, p] = EV_APP
        eq_seq[idxs, p] = eq_ctr[idxs]
        eq_pkt[idxs, p, W.ACK] = WAKE_START
        eq_pkt[idxs, p, W.SEQ] = -1
        eq_pkt[idxs, p, W.SRC] = p
        eq_ctr[idxs] += 1
    state = SimpleNamespace(
        eq_time=eq_time, eq_kind=eq_kind, eq_seq=eq_seq, eq_pkt=eq_pkt,
        eq_ctr=eq_ctr,
        tgen_sync=np.zeros((H, max(tables.sync_slots, 1)), np.int32))
    return SimpleNamespace(cfg=cfg, hp=hp, sh=sh, hosts=state,
                           seed=dep["seed"], host_names=[n for _, n, _ in hosts])
