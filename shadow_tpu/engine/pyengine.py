"""Obviously-correct pure-Python engine for differential testing.

The reference's core testing idea is the dual-run pattern: every test
binary runs natively AND under the simulator, and the results must
agree (SURVEY §4; src/test/CMakeLists.txt). The TPU analogue: the same
scenario runs under (a) the compiled array engine (engine.window) and
(b) this straightforward heap-based Python engine, and the stats must
be IDENTICAL bit for bit.

This engine intentionally mirrors the array engine's semantics —
per-host (time, seq) event order, NIC busy-horizon accounting,
outbox/exchange with per-window budgets and queue-reserve merging, the
counter-keyed loss rolls — but implements them with dicts, lists and a
loop, so each behavior is easy to audit. RNG-derived quantities go
through the same eager jax.random calls, making float rounding
identical.

Covered app tiers: the UDP tier (ping, pingserver, phold, gossip) AND
the TCP tier (bulk, bulkserver, tgen behavior graphs, socks
client/proxy chains — the at-scale flagship). The TCP machine
here is a per-socket-dict transliteration of net.tcp's masked kernels —
handshake, data, SACK scoreboard recovery, RTO go-back-N, congestion
control, FIN/TIME_WAIT — with all float32 congestion math and the SACK
range algebra delegated to the SAME jnp functions (net.congestion,
net.sack) called eagerly, so rounding and truncation match bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as R
from ..core.constants import (HEADER_SIZE_UDPIPETH, HEADER_SIZE_TCPIPETH,
                              MIN_RANDOM_PORT, MAX_PORT, UDP_MAX_PAYLOAD,
                              TCP_MSS, TCP_RTO_INIT, TCP_RTO_MIN, TCP_RTO_MAX,
                              TCP_CLOSE_TIMER_DELAY, SEND_BUFFER_SIZE,
                              RECV_BUFFER_SIZE, SEND_BUFFER_MIN_SIZE,
                              RECV_BUFFER_MIN_SIZE)
from ..core.simtime import (SIMTIME_MAX, SIMTIME_ONE_MICROSECOND,
                            SIMTIME_ONE_SECOND)
from ..net import congestion as CC
from ..net import packet as P
from ..net import sack
from ..net.socket import (TCPS_CLOSED, TCPS_LISTEN, TCPS_SYN_SENT,
                          TCPS_SYN_RECEIVED, TCPS_ESTABLISHED,
                          TCPS_FIN_WAIT_1, TCPS_FIN_WAIT_2, TCPS_CLOSE_WAIT,
                          TCPS_CLOSING, TCPS_LAST_ACK, TCPS_TIME_WAIT,
                          CTL_SYN, CTL_SYNACK, CTL_ACKNOW, CTL_FIN, CTL_RST)
from . import defs
from .defs import (EV_APP, EV_PKT, EV_NIC_TX, EV_TCP_TIMER, EV_TCP_CLOSE,
                   WAKE_START, WAKE_TIMER, WAKE_SOCKET, WAKE_CONNECTED,
                   WAKE_ACCEPT, WAKE_EOF, WAKE_SENT)
from ..apps.base import (APP_NULL, APP_PING, APP_PING_SERVER, APP_PHOLD,
                         APP_GOSSIP, APP_BULK, APP_BULK_SERVER, APP_TGEN,
                         APP_SOCKS_CLIENT, APP_SOCKS_PROXY)
from ..apps import tgen as TG

AUX_FINACK = 1          # net.tcp.AUX_FINACK
_I64MAX = np.iinfo(np.int64).max


def _i32(x):
    """int32 wrap, matching jnp astype(int32) on offsets/casts."""
    return int(np.int32(np.int64(x) & 0xFFFFFFFF))


def _new_sock():
    """One socket row with the engine's alloc-time defaults
    (net.socket.sock_alloc's setf list)."""
    return {
        "used": False, "proto": 0, "state": TCPS_CLOSED,
        "lport": 0, "rport": 0, "rhost": -1, "parent": -1,
        "snd_una": 0, "snd_nxt": 0, "snd_max": 0, "snd_end": 0,
        "rcv_nxt": 0,
        "ooo_s": np.full(sack.K, -1, np.int64),
        "ooo_e": np.full(sack.K, -1, np.int64),
        "sack_s": np.full(sack.K, -1, np.int64),
        "sack_e": np.full(sack.K, -1, np.int64),
        "hole_end": 0, "rex_nxt": 0, "peer_fin": -1,
        "fin_acked": False, "close_after": False,
        "cwnd": np.float32(0.0), "ssthresh": np.float32(0.0),
        "srtt": -1, "rtt_min": -1, "rttvar": 0,
        "rto": TCP_RTO_INIT, "rto_deadline": 0,
        "timer_on": False, "timer_gen": 0, "dupacks": 0,
        "rtt_seq": -1, "rtt_time": 0, "ctl": 0,
        "peer_rwnd": RECV_BUFFER_SIZE,
        "sndbuf": SEND_BUFFER_SIZE, "rcvbuf": RECV_BUFFER_SIZE,
        "hs_time": 0, "last_tx": 0, "syn_tag": 0, "app_ref": -1,
        "proc": 0,
        "cc_wmax": np.float32(0.0), "cc_epoch": -1,
        "cc_k": np.float32(0.0),
    }


class _Host:
    def __init__(self, hid, qcap, scap, txqcap, obcap, procs=1):
        self.hid = hid
        self.qcap = qcap
        self.events = {}      # slot -> (time, seq, kind, pkt)
        self.eq_ctr = 0
        self.rng_ctr = 0
        self.nic_busy = 0
        self.nic_sched = False
        self.nic_rr = 0
        self.nic_rx_until = 0
        self.txq = []
        self.txqcap = txqcap
        self.pkt_ctr = 0
        self.next_eport = MIN_RANDOM_PORT
        self.socks = [_new_sock() for _ in range(scap)]
        self.obcap = obcap
        self.outbox = []             # (send_time, pkt)
        self.ob_next = SIMTIME_MAX   # earliest carried arrival (mirror
        #                              of Hosts.ob_next)
        # per-process app registers (engine app_r [H, PP, 8]); app_r
        # aliases the CURRENT process's list during a dispatch
        self.app_rp = [[0] * 8 for _ in range(max(procs, 1))]
        self.app_r = self.app_rp[0]
        self.cur_proc = 0            # dispatch context (Hosts.app_proc)
        self.tgen_sync = None        # np per-host sync counters (tgen)
        self.free_slots = list(range(qcap))


class PyEngine:
    """Runs a built Simulation's scenario with plain-Python semantics.

    Usage: PyEngine(sim).run() -> stats ndarray comparable to
    sim.run().stats (build two Simulations; each is single-use).
    """

    def __init__(self, sim, count_passes=False):
        cfg = sim.cfg
        self.cfg = cfg
        # lockstep pass recount (obs.passcope differential): when on,
        # run() drains windows in the compiled engine's pass order and
        # tallies {rung label: passes} into self.pass_mix, comparable
        # to SimReport pass_acc / engine.window.pass_labels
        self.count_passes = count_passes
        self.pass_mix = {}
        H = cfg.num_hosts
        self.H = H
        self.hp_vertex = np.asarray(sim.hp.vertex)
        self.hp_bw_up = np.asarray(sim.hp.bw_up)
        self.hp_bw_down = np.asarray(sim.hp.bw_down)
        self.hp_app_kind = np.asarray(sim.hp.app_kind)
        self.hp_app_cfg = np.asarray(sim.hp.app_cfg)
        self.hp_nic_buf = np.asarray(sim.hp.nic_buf)
        self.hp_sndbuf0 = np.asarray(sim.hp.sndbuf0)
        self.hp_rcvbuf0 = np.asarray(sim.hp.rcvbuf0)
        self.lat = np.asarray(sim.sh.lat_ns)
        self.rel = np.asarray(sim.sh.rel)
        self.stop = int(sim.sh.stop_time)
        self.min_jump = int(sim.sh.min_jump)
        self.root = sim.sh.rng_root
        self.reserve = min(8, cfg.qcap // 4)
        self.qdisc = cfg.qdisc
        self.cc_kind = int(np.asarray(sim.sh.cc_kind))
        self.tcp_init_wnd = np.float32(np.asarray(sim.sh.tcp_init_wnd))
        self.tcp_ssthresh0 = np.float32(np.asarray(sim.sh.tcp_ssthresh0))
        # tgen shared tables (zeros when no tgen app)
        self.tg_nodes = np.asarray(sim.sh.tgen_nodes)
        self.tg_peers = np.asarray(sim.sh.tgen_peers)
        self.tg_pool = np.asarray(sim.sh.tgen_pool)
        self.tg_edges = np.asarray(sim.sh.tgen_edges)

        self.stats = np.zeros((H, defs.N_STATS), dtype=np.int64)
        # netscope mirror: always counted here (the python engine has
        # no shape cost); the differential test compares it against
        # the device histograms when cfg.netscope is on
        from ..obs import netscope as _NS
        self._ns = _NS
        self.ns_hist = np.zeros((H, _NS.NS_KINDS, _NS.NS_BUCKETS),
                                dtype=np.int64)
        self.hosts = [_Host(h, cfg.qcap, cfg.scap, cfg.txqcap, cfg.obcap,
                            procs=cfg.procs_per_host)
                      for h in range(H)]
        sync0 = np.asarray(sim.hosts.tgen_sync)
        for h in range(H):
            self.hosts[h].tgen_sync = sync0[h].copy()

        # initial events from the built Simulation state
        eq_time = np.asarray(sim.hosts.eq_time)
        eq_kind = np.asarray(sim.hosts.eq_kind)
        eq_seq = np.asarray(sim.hosts.eq_seq)
        eq_pkt = np.asarray(sim.hosts.eq_pkt)
        eq_ctr = np.asarray(sim.hosts.eq_ctr)
        for h in range(H):
            host = self.hosts[h]
            host.eq_ctr = int(eq_ctr[h])
            for s in range(cfg.qcap):
                if eq_time[h, s] != SIMTIME_MAX:
                    host.free_slots.remove(s)
                    host.events[s] = (int(eq_time[h, s]), int(eq_seq[h, s]),
                                      int(eq_kind[h, s]),
                                      eq_pkt[h, s].copy())

        self.seed32 = int(sim.seed) & 0xFFFFFFFF

    # --- RNG: exact Python-int mirror of core.rng's cheap PRNG ---
    @staticmethod
    def _mix32(x):
        M = 0xFFFFFFFF
        x &= M
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & M
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & M
        return x ^ (x >> 16)

    def _stream_of(self, domain, ident):
        M = 0xFFFFFFFF
        s = ((self.seed32 * 0x9E3779B9) ^ (domain * 0x85EBCA6B) ^
             ((ident & M) * 0xC2B2AE35)) & M
        return self._mix32(s)

    def _cheap_uniform(self, stream, counter):
        bits = self._mix32(stream ^ ((counter + 0x9E3779B9) & 0xFFFFFFFF))
        return np.float32(bits >> 8) * np.float32(1.0 / (1 << 24))

    def _draw(self, host):
        stream = self._stream_of(R.DOMAIN_HOST, host.hid)
        u = self._cheap_uniform(stream, host.rng_ctr)
        host.rng_ctr += 1
        return u  # np.float32, bit-identical to the device value

    # --- event queue (first-free-slot + (time, seq) order) ---
    def _q_push(self, host, t, kind, pkt):
        if not host.free_slots:
            self.stats[host.hid, defs.ST_EQ_FULL_LOCAL] += 1
            host.eq_ctr += 1
            return
        slot = min(host.free_slots)
        host.free_slots.remove(slot)
        host.events[slot] = (int(t), host.eq_ctr, kind, pkt)
        host.eq_ctr += 1

    def _q_pop_min(self, host):
        slot = min(host.events,
                   key=lambda s: (host.events[s][0], host.events[s][1]))
        ev = host.events.pop(slot)
        host.free_slots.append(slot)
        return ev

    def _next_time(self, host):
        if not host.events:
            return SIMTIME_MAX
        return min(t for t, _, _, _ in host.events.values())

    # --- socket table (net.socket mirror) ---
    def _sock_alloc(self, host, proto):
        """Mirror of sock_alloc: first free row, else recycle the
        longest-resident TIME_WAIT row. Returns (slot, ok)."""
        free = [i for i, s in enumerate(host.socks) if not s["used"]]
        tw = [i for i, s in enumerate(host.socks)
              if s["used"] and s["state"] == TCPS_TIME_WAIT]
        ok = bool(free) or bool(tw)
        if free:
            slot = free[0]
        elif tw:
            slot = min(tw, key=lambda i: (host.socks[i]["last_tx"], i))
        else:
            slot = 0  # argmin of all-int64max ranks
        if ok:
            gen = host.socks[slot]["timer_gen"] + 1
            host.socks[slot] = _new_sock()
            host.socks[slot]["used"] = True
            host.socks[slot]["proto"] = proto
            host.socks[slot]["timer_gen"] = gen
            host.socks[slot]["proc"] = host.cur_proc
        return slot, ok

    @staticmethod
    def _sock_free(host, slot):
        """Mirror of sock_free: clears flags only, bumps generation
        (other fields stay stale until the next alloc)."""
        sk = host.socks[slot]
        sk["used"] = False
        sk["proto"] = 0
        sk["state"] = TCPS_CLOSED
        sk["ctl"] = 0
        sk["rto_deadline"] = 0
        sk["timer_on"] = False
        sk["timer_gen"] += 1
        sk["app_ref"] = -1

    def _alloc_eport(self, host):
        span = MAX_PORT - MIN_RANDOM_PORT
        p = host.next_eport
        for _ in range(4):
            if any(s["used"] and s["lport"] == p for s in host.socks):
                p = MIN_RANDOM_PORT + (p + 1 - MIN_RANDOM_PORT) % span
        host.next_eport = MIN_RANDOM_PORT + (p + 1 - MIN_RANDOM_PORT) % span
        return p

    def _udp_open(self, host, port=None):
        slot, ok = self._sock_alloc(host, P.PROTO_UDP)
        eport = self._alloc_eport(host) if port is None else int(port)
        if ok:
            host.socks[slot]["lport"] = eport
        return slot if ok else -1

    def _demux(self, host, src, sport, dport, proto):
        """Mirror of sock_demux: exact 4-tuple first, then listening
        (TCP) / unconnected (UDP) fallback; lowest slot wins."""
        exact = fb = -1
        for i, s in enumerate(host.socks):
            if not s["used"] or s["proto"] != proto or s["lport"] != dport:
                continue
            if (s["rhost"] == src and s["rport"] == sport and exact < 0):
                exact = i
            if proto == P.PROTO_TCP:
                if s["state"] == TCPS_LISTEN and fb < 0:
                    fb = i
            elif s["rhost"] == -1 and fb < 0:
                fb = i
        return exact if exact >= 0 else fb

    # --- NIC (net.nic mirror) ---
    @staticmethod
    def _tx_dur(nbytes, bw):
        return (int(nbytes) * SIMTIME_ONE_SECOND) // max(int(bw), 1)

    @staticmethod
    def _wire_bytes(pkt):
        proto = int(pkt[P.FLAGS]) & P.PROTO_MASK
        hdr = (HEADER_SIZE_TCPIPETH if proto == P.PROTO_TCP
               else HEADER_SIZE_UDPIPETH)
        return int(pkt[P.LEN]) + hdr

    def _udp_sendto(self, host, now, slot, dst, dport, nbytes, aux=0):
        length = min(int(nbytes), UDP_MAX_PAYLOAD)
        pkt = np.zeros(P.PKT_WORDS, dtype=np.int32)
        pkt[P.SRC] = host.hid
        pkt[P.DST] = int(dst)
        pkt[P.SPORT] = host.socks[slot]["lport"]
        pkt[P.DPORT] = int(dport)
        pkt[P.FLAGS] = P.PROTO_UDP
        pkt[P.LEN] = length
        pkt[P.AUX] = np.int32(np.int64(aux) & 0xFFFFFFFF)
        host.socks[slot]["snd_end"] += length
        if len(host.txq) < host.txqcap:
            host.txq.append(pkt)
        else:
            self.stats[host.hid, defs.ST_TXQ_DROP] += 1
        self._kick(host, now)

    def _tcp_want_tx(self, sk):
        """Mirror of tcp_want_tx for one socket dict."""
        st = sk["state"]
        open_tx = st in (TCPS_ESTABLISHED, TCPS_CLOSE_WAIT)
        data_tx = st in (TCPS_ESTABLISHED, TCPS_CLOSE_WAIT, TCPS_FIN_WAIT_1,
                         TCPS_CLOSING, TCPS_LAST_ACK)
        cw = int(sk["cwnd"]) * TCP_MSS
        win = min(cw, max(sk["peer_rwnd"], 1))
        if data_tx and sk["hole_end"] > 0:
            # the eager sack calls only matter inside an open recovery
            # episode (hole_end > 0); rex_ok is False otherwise anyway
            rex_tgt = int(sack.skip(np.int64(sk["rex_nxt"]),
                                    jnp.asarray(sk["sack_s"]),
                                    jnp.asarray(sk["sack_e"])))
            lost_end = int(sack.lost_bound(jnp.asarray(sk["sack_s"]),
                                           jnp.asarray(sk["sack_e"]),
                                           np.int64(sk["snd_una"]),
                                           np.int64(sk["hole_end"])))
            rex_ok = rex_tgt < lost_end
        else:
            rex_ok = False
        data_ok = (data_tx and sk["snd_nxt"] < sk["snd_end"] and
                   sk["snd_nxt"] < sk["snd_una"] + win)
        fin_due = (open_tx and sk["close_after"] and
                   sk["snd_nxt"] == sk["snd_end"])
        return sk["proto"] == P.PROTO_TCP and (rex_ok or data_ok or fin_due)

    def _tx_want(self, host):
        """[S] mirror of nic.tx_want."""
        return [s["used"] and (s["ctl"] != 0 or self._tcp_want_tx(s))
                for s in host.socks]

    def _has_work(self, host):
        return bool(host.txq) or any(self._tx_want(host))

    def _kick(self, host, now):
        if self._has_work(host) and not host.nic_sched:
            ok = bool(host.free_slots)
            self._q_push(host, max(now, host.nic_busy), EV_NIC_TX,
                         np.zeros(P.PKT_WORDS, np.int32))
            host.nic_sched = ok

    def _on_tx(self, host, now, wend):
        host.nic_sched = False
        if len(host.outbox) >= host.obcap:
            ok = bool(host.free_slots)
            self._q_push(host, max(wend, now + 1), EV_NIC_TX,
                         np.zeros(P.PKT_WORDS, np.int32))
            host.nic_sched = ok
            return
        self._tx_pull(host, now)

    def _tx_pull(self, host, now):
        """Mirror of nic._tx_pull: ring first, else qdisc-selected TCP
        socket via tcp_pull; emit; bandwidth; reschedule. The socket
        want-scan (2 eager sack dispatches per TCP socket) runs only
        when the ring cannot supply the packet — the compiled engine
        computes it unconditionally but discards it, so skipping it here
        is behavior-identical and removes most of this hot path's cost."""
        S = len(host.socks)
        if host.txq:
            out_pkt, has_pkt = host.txq.pop(0), True
        else:
            want = self._tx_want(host)
            if any(want):
                if self.qdisc == 1:  # QDISC_RR
                    sock = min((((i - host.nic_rr) % S), i)
                               for i in range(S) if want[i])[1]
                else:                # FIFO: least recently served
                    sock = min((host.socks[i]["last_tx"] * S + i, i)
                               for i in range(S) if want[i])[1]
                out_pkt, has_pkt = self._tcp_pull(host, now, sock)
                if has_pkt:
                    host.nic_rr = (sock + 1) % S
            else:
                out_pkt, has_pkt = None, False

        busy_end = now
        if has_pkt and out_pkt is not None:
            wire = self._wire_bytes(out_pkt)
            busy_end = now + max(self._tx_dur(wire,
                                              self.hp_bw_up[host.hid]), 1)
            self._emit(host, now, out_pkt)
        elif has_pkt:
            # tcp_pull claimed has but produced nothing — cannot happen
            has_pkt = False
        host.nic_busy = busy_end
        if has_pkt and self._has_work(host):
            ok = bool(host.free_slots)
            self._q_push(host, busy_end, EV_NIC_TX,
                         np.zeros(P.PKT_WORDS, np.int32))
            host.nic_sched = ok

    def _emit(self, host, now, pkt):
        pkt = pkt.copy()
        pkt[P.UID] = host.pkt_ctr
        if int(pkt[P.DST]) == host.hid:
            self._q_push(host, now + 1, EV_PKT, pkt)  # loopback, 1ns
        else:
            if len(host.outbox) < host.obcap:
                host.outbox.append((now, pkt))
            else:
                self.stats[host.hid, defs.ST_OUTBOX_DROP] += 1
        self.stats[host.hid, defs.ST_PKTS_SENT] += 1
        host.pkt_ctr += 1

    def _ns_observe(self, hid, kind, value_us):
        """Host-side mirror of obs.netscope.observe (same bucketing)."""
        self.ns_hist[hid, kind, self._ns.bucket_of(value_us)] += 1

    def _on_pkt(self, host, now, pkt):
        wire = self._wire_bytes(pkt)
        bw = max(int(self.hp_bw_down[host.hid]), 1)
        backlog_ns = max(host.nic_rx_until - now, 0)
        backlog_bytes = (backlog_ns * bw) // SIMTIME_ONE_SECOND
        if backlog_bytes + wire > int(self.hp_nic_buf[host.hid]):
            self.stats[host.hid, defs.ST_PKTS_DROP_BUF] += 1
            return
        self._ns_observe(host.hid, self._ns.NS_QUEUE, backlog_ns // 1000)
        host.nic_rx_until = max(host.nic_rx_until, now) + \
            self._tx_dur(wire, bw)
        self.stats[host.hid, defs.ST_PKTS_RECV] += 1
        proto = int(pkt[P.FLAGS]) & P.PROTO_MASK
        if proto == P.PROTO_TCP:
            slot = self._demux(host, int(pkt[P.SRC]), int(pkt[P.SPORT]),
                               int(pkt[P.DPORT]), P.PROTO_TCP)
            if slot >= 0:
                self._tcp_rx(host, now, slot, pkt)
            return
        slot = self._demux(host, int(pkt[P.SRC]), int(pkt[P.SPORT]),
                           int(pkt[P.DPORT]), P.PROTO_UDP)
        if slot < 0:
            return
        host.socks[slot]["rcv_nxt"] += int(pkt[P.LEN])
        self.stats[host.hid, defs.ST_BYTES_RECV] += int(pkt[P.LEN])
        wake = pkt.copy()
        wake[P.SEQ] = slot
        wake[P.ACK] = WAKE_SOCKET
        wake[P.WND] = host.socks[slot]["timer_gen"]
        self._q_push(host, now + 1, EV_APP, wake)

    # --- TCP machine (net.tcp transliteration) -----------------------------
    # Each function mirrors its namesake in net/tcp.py statement by
    # statement; float32 congestion math and SACK range algebra call the
    # SAME jnp code eagerly so rounding/truncation are bit-identical.

    def _wake(self, host, now, reason, slot, pkt=None, ln=0, aux=0):
        w = (np.zeros(P.PKT_WORDS, np.int32) if pkt is None
             else pkt.copy())
        w[P.ACK] = reason
        w[P.SEQ] = slot
        w[P.LEN] = _i32(ln)
        w[P.AUX] = _i32(aux)
        w[P.WND] = host.socks[slot]["timer_gen"]
        self._q_push(host, now + 1, EV_APP, w)

    def _arm_timer(self, host, slot, now):
        sk = host.socks[slot]
        deadline = now + sk["rto"]
        sk["rto_deadline"] = deadline
        if not sk["timer_on"]:
            ok = bool(host.free_slots)
            ev = np.zeros(P.PKT_WORDS, np.int32)
            ev[P.SEQ] = slot
            ev[P.ACK] = sk["timer_gen"]
            self._q_push(host, deadline, EV_TCP_TIMER, ev)
            sk["timer_on"] = ok

    def _tcp_listen(self, host, port):
        slot, ok = self._sock_alloc(host, P.PROTO_TCP)
        if ok:
            host.socks[slot]["state"] = TCPS_LISTEN
            host.socks[slot]["lport"] = int(port)
        return slot, ok

    def _tcp_connect(self, host, now, dst_host, dst_port, tag=0):
        slot, ok = self._sock_alloc(host, P.PROTO_TCP)
        lport = self._alloc_eport(host)   # unconditional, like the engine
        if ok:
            sk = host.socks[slot]
            sk["state"] = TCPS_SYN_SENT
            sk["lport"] = lport
            sk["rport"] = int(dst_port)
            sk["rhost"] = int(dst_host)
            sk["ctl"] = CTL_SYN
            sk["cwnd"] = self.tcp_init_wnd
            sk["ssthresh"] = self.tcp_ssthresh0
            sk["hs_time"] = now
            sk["syn_tag"] = _i32(tag)
            self._arm_timer(host, slot, now)
            self._kick(host, now)
        else:
            self.stats[host.hid, defs.ST_SOCK_FAIL] += 1
        return slot, ok

    def _tcp_write(self, host, now, slot, nbytes):
        host.socks[slot]["snd_end"] += int(nbytes)
        self._kick(host, now)

    def _tcp_close_call(self, host, now, slot):
        sk = host.socks[slot]
        if sk["state"] in (TCPS_LISTEN, TCPS_CLOSED, TCPS_SYN_SENT,
                           TCPS_SYN_RECEIVED):
            self._sock_free(host, slot)
        else:
            sk["close_after"] = True
            self._kick(host, now)

    def _finack_aux(self, sk):
        pf = sk["peer_fin"]
        got_fin = pf >= 0 and sk["rcv_nxt"] >= pf
        aux = AUX_FINACK if got_fin else 0
        b1, b2 = sack.encode2(jnp.asarray(sk["ooo_s"]),
                              jnp.asarray(sk["ooo_e"]),
                              np.int64(sk["rcv_nxt"]))
        return aux | int(b1), int(b2)

    def _tcp_pull(self, host, now, slot):
        """Mirror of tcp_pull. Returns (pkt or None, has)."""
        sk = host.socks[slot]
        state = sk["state"]
        ctl = sk["ctl"]
        open_tx = state in (TCPS_ESTABLISHED, TCPS_CLOSE_WAIT)
        data_tx = state in (TCPS_ESTABLISHED, TCPS_CLOSE_WAIT,
                            TCPS_FIN_WAIT_1, TCPS_CLOSING, TCPS_LAST_ACK)

        snd_nxt = sk["snd_nxt"]
        snd_end = sk["snd_end"]
        cw = int(sk["cwnd"]) * TCP_MSS
        limit = sk["snd_una"] + min(cw, max(sk["peer_rwnd"], 1))
        hole_end = sk["hole_end"]
        sck_s = jnp.asarray(sk["sack_s"])
        sck_e = jnp.asarray(sk["sack_e"])
        rex_nxt = int(sack.skip(np.int64(sk["rex_nxt"]), sck_s, sck_e))
        lost_end = int(sack.lost_bound(sck_s, sck_e,
                                       np.int64(sk["snd_una"]),
                                       np.int64(hole_end)))
        rex_pending = data_tx and hole_end > 0 and rex_nxt < lost_end
        can_new = data_tx and snd_nxt < snd_end and snd_nxt < limit
        can_data = rex_pending or can_new

        fin_first = (open_tx and sk["close_after"] and snd_nxt == snd_end)
        fin_rexmit = (ctl & CTL_FIN) != 0 and state in (
            TCPS_FIN_WAIT_1, TCPS_CLOSING, TCPS_LAST_ACK)

        if ctl & CTL_RST:
            sel = 0
        elif ctl & CTL_SYN:
            sel = 1
        elif ctl & CTL_SYNACK:
            sel = 2
        elif can_data:
            sel = 3
        elif fin_first or fin_rexmit:
            sel = 4
        elif ctl & CTL_ACKNOW:
            sel = 5
        else:
            sel = -1
        has = sel >= 0

        ack_no = _i32(sk["rcv_nxt"])
        wnd = _i32(min(sk["rcvbuf"], 2**31 - 1))
        aux, sack2 = self._finack_aux(sk)
        if sel in (1, 2):
            # handshake bandwidth stamp (net.tcp.tcp_pull)
            aux = ((min(int(self.hp_bw_up[host.hid]) >> 10, 0xFFFF)
                    << 16) |
                   min(int(self.hp_bw_down[host.hid]) >> 10, 0xFFFF))

        rex_cap = min(lost_end,
                      int(sack.next_start_after(np.int64(rex_nxt),
                                                sck_s, sck_e)))
        if sel == 3:
            ln = (min(TCP_MSS, rex_cap - rex_nxt) if rex_pending
                  else min(TCP_MSS, min(snd_end, limit) - snd_nxt))
        else:
            ln = 0
        seq = (rex_nxt if rex_pending else snd_nxt) if sel == 3 \
            else (snd_end if sel == 4 else 0)
        flags = P.PROTO_TCP
        if sel in (1, 2):
            flags |= P.F_SYN
        if sel == 0:
            flags |= P.F_RST
        if sel == 4:
            flags |= P.F_FIN
        if sel == 2 or sel >= 3:
            flags |= P.F_ACK

        is_resend = sel == 3 and (rex_pending or snd_nxt < sk["snd_max"])
        pkt = np.zeros(P.PKT_WORDS, np.int32)
        pkt[P.SRC] = host.hid
        pkt[P.DST] = sk["rhost"]
        pkt[P.SPORT] = sk["lport"]
        pkt[P.DPORT] = sk["rport"]
        pkt[P.FLAGS] = flags
        pkt[P.SEQ] = _i32(seq)
        pkt[P.ACK] = ack_no
        pkt[P.WND] = wnd
        pkt[P.LEN] = _i32(ln)
        pkt[P.AUX] = _i32(aux)
        pkt[P.APP] = _i32(sk["syn_tag"] if sel == 1 else sack2)
        pkt[P.STATUS] = P.DS_CREATED | (P.DS_RETRANS if is_resend else 0)

        clr = {0: CTL_RST, 1: CTL_SYN, 2: CTL_SYNACK, 4: CTL_FIN}.get(sel, 0)
        if sel == 2 or sel >= 3:
            clr |= CTL_ACKNOW
        sk["ctl"] = ctl & ~clr
        sk["last_tx"] = now

        is_data = sel == 3
        is_rex = is_data and rex_pending
        snd_max = sk["snd_max"]
        new_nxt = snd_nxt + ln
        advance = is_data and not is_rex and new_nxt > snd_max
        gbn = is_data and not is_rex and snd_nxt < snd_max
        if advance:
            self.stats[host.hid, defs.ST_BYTES_SENT] += \
                new_nxt - max(snd_max, snd_nxt)
        if is_rex or gbn:
            self.stats[host.hid, defs.ST_RETRANSMIT] += 1
            self._ns_observe(host.hid, self._ns.NS_RETX,
                             sk["rto"] // 1000)
        time_it = is_data and not is_rex and not gbn and sk["rtt_seq"] < 0
        if is_data and not is_rex:
            sk["snd_nxt"] = new_nxt
        sk["rex_nxt"] = rex_nxt + (ln if is_rex else 0)
        if advance:
            sk["snd_max"] = new_nxt
        if time_it:
            sk["rtt_seq"] = new_nxt
            sk["rtt_time"] = now

        if sel == 4:
            if state == TCPS_ESTABLISHED:
                sk["state"] = TCPS_FIN_WAIT_1
            elif state == TCPS_CLOSE_WAIT:
                sk["state"] = TCPS_LAST_ACK

        if sel == 0:
            self._sock_free(host, slot)
        if sel in (1, 2) or is_data or sel == 4:
            self._arm_timer(host, slot, now)
        return (pkt if has else None), has

    def _autotune(self, host, slot, pkt):
        """Mirror of net.tcp._autotune: peer bandwidths from the
        handshake AUX stamp, RTT = 2x the SEQ latency stamp."""
        sk = host.socks[slot]
        peer = int(pkt[P.SRC])
        rtt_us = 2 * max(int(pkt[P.SEQ]), 0)
        peer_up = ((int(pkt[P.AUX]) >> 16) & 0xFFFF) << 10
        peer_dn = (int(pkt[P.AUX]) & 0xFFFF) << 10
        bw_cap = 1 << 38
        snd_bw = min(int(self.hp_bw_up[host.hid]), peer_dn, bw_cap)
        rcv_bw = min(int(self.hp_bw_down[host.hid]), peer_up, bw_cap)
        buf_cap = 1 << 30
        sndbuf_auto = min(max((snd_bw * rtt_us // 1_000_000) * 5 // 4,
                              SEND_BUFFER_MIN_SIZE), buf_cap)
        rcvbuf_auto = min(max((rcv_bw * rtt_us // 1_000_000) * 5 // 4,
                              RECV_BUFFER_MIN_SIZE), buf_cap)
        if peer == host.hid:
            sndbuf_auto = rcvbuf_auto = 16 * 1024 * 1024
        sb0 = int(self.hp_sndbuf0[host.hid])
        rb0 = int(self.hp_rcvbuf0[host.hid])
        sk["sndbuf"] = sb0 if sb0 >= 0 else sndbuf_auto
        sk["rcvbuf"] = rb0 if rb0 >= 0 else rcvbuf_auto

    @staticmethod
    def _rfc6298(srtt, rttvar, sample):
        first = srtt < 0
        srtt1 = sample if first else (7 * srtt + sample) // 8
        rttvar1 = (sample // 2 if first
                   else (3 * rttvar + abs(srtt - sample)) // 4)
        rto = min(max(srtt1 + max(4 * rttvar1, 1), TCP_RTO_MIN), TCP_RTO_MAX)
        return srtt1, rttvar1, rto

    def _accept_syn(self, host, now, lslot, pkt):
        child, ok = self._sock_alloc(host, P.PROTO_TCP)
        if not ok:
            self.stats[host.hid, defs.ST_SOCK_FAIL] += 1
            return
        sk = host.socks[child]
        sk["state"] = TCPS_SYN_RECEIVED
        sk["lport"] = int(pkt[P.DPORT])
        sk["rport"] = int(pkt[P.SPORT])
        sk["rhost"] = int(pkt[P.SRC])
        sk["parent"] = lslot
        sk["proc"] = host.socks[lslot]["proc"]   # inherit owner
        sk["ctl"] = CTL_SYNACK
        sk["cwnd"] = self.tcp_init_wnd
        sk["ssthresh"] = self.tcp_ssthresh0
        sk["peer_rwnd"] = max(int(pkt[P.WND]), 1)
        sk["hs_time"] = now
        sk["syn_tag"] = int(pkt[P.APP])
        self._autotune(host, child, pkt)
        self._arm_timer(host, child, now)

    def _rx_conn(self, host, now, slot, pkt):
        sk = host.socks[slot]
        flags = int(pkt[P.FLAGS])
        syn = (flags & P.F_SYN) != 0
        ackf = (flags & P.F_ACK) != 0
        fin = (flags & P.F_FIN) != 0
        seq = int(pkt[P.SEQ])
        ackno = int(pkt[P.ACK])
        ln = int(pkt[P.LEN])
        # AUX is the bw stamp on handshake segments: FINACK only on ~syn
        finack = (not syn) and (int(pkt[P.AUX]) & AUX_FINACK) != 0

        state0 = sk["state"]

        # --- A. establishment ---
        estA = state0 == TCPS_SYN_SENT and syn and ackf
        estB = state0 == TCPS_SYN_RECEIVED and ackf and not syn
        resyn = state0 == TCPS_SYN_RECEIVED and syn and not ackf
        resynack = state0 >= TCPS_ESTABLISHED and syn and ackf
        state1 = TCPS_ESTABLISHED if (estA or estB) else state0
        est = estA or estB

        sk["state"] = state1
        if estA:
            sk["ctl"] |= CTL_ACKNOW
        if resyn:
            sk["ctl"] |= CTL_SYNACK
        if resynack:
            sk["ctl"] |= CTL_ACKNOW
        if est:
            hs_rtt = now - sk["hs_time"]
            sk["srtt"], sk["rttvar"], sk["rto"] = self._rfc6298(
                sk["srtt"], sk["rttvar"], hs_rtt)
            sk["rtt_min"] = (min(sk["rtt_min"], hs_rtt)
                             if sk["rtt_min"] > 0 else hs_rtt)
            sk["rto_deadline"] = 0
            self._wake(host, now,
                       WAKE_CONNECTED if estA else WAKE_ACCEPT, slot,
                       pkt=pkt)

        # --- A2. buffer autotuning: active side on the SYN|ACK; the
        # passive side tuned at child creation (_accept_syn) ---
        if estA:
            self._autotune(host, slot, pkt)

        # --- B. ACK processing ---
        conn = state1 >= TCPS_ESTABLISHED
        valid_ack = ackf and conn
        snd_una0 = sk["snd_una"]
        snd_end = sk["snd_end"]
        new_ack = valid_ack and ackno > snd_una0
        acked_bytes = max(ackno - snd_una0, 0)
        npkts = (acked_bytes + TCP_MSS - 1) // TCP_MSS
        snd_una1 = ackno if new_ack else snd_una0

        snd_max0 = sk["snd_max"]
        upd = valid_ack and not syn
        b1s, b1e = sack.decode(np.int32(pkt[P.AUX]), np.int64(ackno),
                               np.int64(snd_max0))
        b2s, b2e = sack.decode(np.int32(pkt[P.APP]), np.int64(ackno),
                               np.int64(snd_max0))
        sb_s = jnp.asarray(sk["sack_s"])
        sb_e = jnp.asarray(sk["sack_e"])
        sb_s, sb_e = sack.insert(sb_s, sb_e,
                                 jnp.where(upd, b1s, -1),
                                 jnp.where(upd, b1e, -2))
        sb_s, sb_e = sack.insert(sb_s, sb_e,
                                 jnp.where(upd, b2s, -1),
                                 jnp.where(upd, b2e, -2))
        sb_s, sb_e = sack.drop_below(sb_s, sb_e, np.int64(snd_una1))
        sk["sack_s"] = np.asarray(sb_s)
        sk["sack_e"] = np.asarray(sb_e)

        rtt_seq = sk["rtt_seq"]
        sample_ok = new_ack and rtt_seq >= 0 and ackno >= rtt_seq
        dup = (valid_ack and ackno == snd_una0 and ln == 0 and not syn
               and not fin and sk["snd_nxt"] > snd_una0)
        dupacks1 = 0 if new_ack else sk["dupacks"] + (1 if dup else 0)
        fast_rx = dup and dupacks1 == 3

        cw0, ss0 = sk["cwnd"], sk["ssthresh"]
        wm0, ep0, k0 = sk["cc_wmax"], sk["cc_epoch"], sk["cc_k"]
        if new_ack:
            # delayMin for the rate cap (pre-this-sample, as on device)
            delay_ns = sk["rtt_min"] if sk["rtt_min"] > 0 else sk["srtt"]
            cw_a, ep_a, k_a = CC.on_ack(
                jnp.int32(self.cc_kind), jnp.float32(cw0), jnp.float32(ss0),
                jnp.float32(wm0), jnp.int64(ep0), jnp.float32(k0),
                jnp.int64(npkts), jnp.int64(now), jnp.int64(delay_ns))
            cw_a, ep_a, k_a = (np.float32(cw_a), int(ep_a), np.float32(k_a))
        if fast_rx:
            cw_l, ss_l, wm_l, ep_l = CC.on_loss(
                jnp.int32(self.cc_kind), jnp.float32(cw0), jnp.float32(ss0),
                jnp.float32(wm0))
            cw_l, ss_l, wm_l, ep_l = (np.float32(cw_l), np.float32(ss_l),
                                      np.float32(wm_l), int(ep_l))

        sk["snd_una"] = snd_una1
        sk["dupacks"] = dupacks1
        if valid_ack:
            sk["peer_rwnd"] = max(int(pkt[P.WND]), 1)
        if sample_ok:
            rtt_sample = max(now - sk["rtt_time"], 1)
            sk["srtt"], sk["rttvar"], sk["rto"] = self._rfc6298(
                sk["srtt"], sk["rttvar"], rtt_sample)
            sk["rtt_min"] = (min(sk["rtt_min"], rtt_sample)
                             if sk["rtt_min"] > 0 else rtt_sample)
            sk["rtt_seq"] = -1
        if fast_rx:
            sk["cwnd"], sk["ssthresh"] = cw_l, ss_l
            sk["cc_wmax"], sk["cc_epoch"] = wm_l, ep_l
            sk["hole_end"] = snd_max0
            sk["rex_nxt"] = ackno
        else:
            if new_ack:
                sk["cwnd"], sk["cc_epoch"], sk["cc_k"] = cw_a, ep_a, k_a
                if ackno >= sk["hole_end"]:
                    sk["hole_end"] = 0
                sk["rex_nxt"] = max(sk["rex_nxt"], ackno)

        # our FIN acked?
        fin_done = valid_ack and finack and ackno >= snd_end
        fin_acked1 = sk["fin_acked"] or fin_done
        state2 = state1
        if fin_acked1 and state1 == TCPS_FIN_WAIT_1:
            state2 = TCPS_FIN_WAIT_2
        elif fin_acked1 and state1 == TCPS_CLOSING:
            state2 = TCPS_TIME_WAIT
        elif fin_acked1 and state1 == TCPS_LAST_ACK:
            state2 = TCPS_CLOSED
        sk["fin_acked"] = fin_acked1
        sk["state"] = state2

        flight = (sk["snd_nxt"] > snd_una1 or
                  (state2 in (TCPS_FIN_WAIT_1, TCPS_CLOSING, TCPS_LAST_ACK)
                   and not fin_acked1))
        if valid_ack:
            sk["rto_deadline"] = (now + sk["rto"]) if flight else 0

        sent_all = new_ack and ackno >= snd_end and snd_end > 0
        if sent_all:
            self._wake(host, now, WAKE_SENT, slot, pkt=pkt)

        # --- C. data ---
        can_rx = state2 in (TCPS_ESTABLISHED, TCPS_FIN_WAIT_1,
                            TCPS_FIN_WAIT_2)
        has_data = ln > 0 and can_rx
        rcv0 = sk["rcv_nxt"]
        seg_end = seq + ln

        in_order = has_data and seq <= rcv0 and seg_end > rcv0
        adv = seg_end if in_order else rcv0
        oos, ooe, rcv1 = sack.consume(jnp.asarray(sk["ooo_s"]),
                                      jnp.asarray(sk["ooo_e"]),
                                      np.int64(adv))
        rcv1 = int(rcv1)
        is_ooo = has_data and seq > rcv1
        oos, ooe, reneged = sack.insert_counted(
            oos, ooe,
            np.int64(seq if is_ooo else -1),
            np.int64(seg_end if is_ooo else -2))
        sk["ooo_s"] = np.asarray(oos)
        sk["ooo_e"] = np.asarray(ooe)

        delivered = rcv1 - rcv0
        sk["rcv_nxt"] = rcv1
        if ln > 0 or fin:
            sk["ctl"] |= CTL_ACKNOW
        self.stats[host.hid, defs.ST_BYTES_RECV] += delivered
        self.stats[host.hid, defs.ST_SACK_RENEGE] += int(reneged)
        if delivered > 0:
            self._wake(host, now, WAKE_SOCKET, slot, pkt=pkt,
                       ln=delivered, aux=int(pkt[P.AUX]))

        # --- D. peer FIN ---
        fin_valid = fin and state2 >= TCPS_ESTABLISHED
        peer_fin1 = seq if (fin_valid and sk["peer_fin"] < 0) \
            else sk["peer_fin"]
        fin_complete = peer_fin1 >= 0 and rcv1 >= peer_fin1
        eof_now = fin_complete and state2 in (
            TCPS_ESTABLISHED, TCPS_FIN_WAIT_1, TCPS_FIN_WAIT_2)
        state3 = state2
        if eof_now and state2 == TCPS_ESTABLISHED:
            state3 = TCPS_CLOSE_WAIT
        elif eof_now and state2 == TCPS_FIN_WAIT_1:
            state3 = TCPS_TIME_WAIT if fin_acked1 else TCPS_CLOSING
        elif eof_now and state2 == TCPS_FIN_WAIT_2:
            state3 = TCPS_TIME_WAIT
        sk["peer_fin"] = peer_fin1
        sk["state"] = state3
        if eof_now:
            self._wake(host, now, WAKE_EOF, slot, pkt=pkt)

        # --- E. terminal bookkeeping ---
        if state3 == TCPS_TIME_WAIT and state0 != TCPS_TIME_WAIT:
            ev = np.zeros(P.PKT_WORDS, np.int32)
            ev[P.SEQ] = slot
            ev[P.ACK] = sk["timer_gen"]
            self._q_push(host, now + TCP_CLOSE_TIMER_DELAY,
                         EV_TCP_CLOSE, ev)
            sk["rto_deadline"] = 0
        if state3 == TCPS_CLOSED:
            self._sock_free(host, slot)

    def _tcp_rx(self, host, now, slot, pkt):
        flags = int(pkt[P.FLAGS])
        syn = (flags & P.F_SYN) != 0
        ackf = (flags & P.F_ACK) != 0
        rst = (flags & P.F_RST) != 0
        state = host.socks[slot]["state"]
        if rst:
            if state >= TCPS_ESTABLISHED:
                self._wake(host, now, WAKE_EOF, slot, pkt=pkt)
            self._sock_free(host, slot)
        elif state == TCPS_LISTEN and syn and not ackf:
            self._accept_syn(host, now, slot, pkt)
        else:
            self._rx_conn(host, now, slot, pkt)
        self._kick(host, now)

    def _on_tcp_timer(self, host, now, ev):
        slot = int(ev[P.SEQ])
        gen = int(ev[P.ACK])
        sk = host.socks[slot]
        if not (sk["used"] and gen == sk["timer_gen"] and
                sk["proto"] == P.PROTO_TCP):
            return
        deadline = sk["rto_deadline"]
        if deadline == 0:
            sk["timer_on"] = False
            return
        if now < deadline:
            ev2 = np.zeros(P.PKT_WORDS, np.int32)
            ev2[P.SEQ] = slot
            ev2[P.ACK] = gen
            self._q_push(host, deadline, EV_TCP_TIMER, ev2)
            return
        # expired: backoff, handshake/FIN control resends, go-back-N
        state = sk["state"]
        sk["rto"] = min(sk["rto"] * 2, TCP_RTO_MAX)
        if state == TCPS_SYN_SENT:
            sk["ctl"] |= CTL_SYN
        if state == TCPS_SYN_RECEIVED:
            sk["ctl"] |= CTL_SYNACK
        if state in (TCPS_FIN_WAIT_1, TCPS_CLOSING, TCPS_LAST_ACK) \
                and not sk["fin_acked"]:
            sk["ctl"] |= CTL_FIN
        had_flight = sk["snd_nxt"] > sk["snd_una"]
        if had_flight:
            cw_l, ss_l, wm_l, ep_l = CC.on_loss(
                jnp.int32(self.cc_kind), jnp.float32(sk["cwnd"]),
                jnp.float32(sk["ssthresh"]), jnp.float32(sk["cc_wmax"]))
            sk["cwnd"] = np.float32(cw_l)
            sk["ssthresh"] = np.float32(ss_l)
            sk["cc_wmax"] = np.float32(wm_l)
            sk["cc_epoch"] = int(ep_l)
            sk["snd_nxt"] = sk["snd_una"]
        sk["hole_end"] = 0
        sk["sack_s"] = np.full(sack.K, -1, np.int64)
        sk["sack_e"] = np.full(sack.K, -1, np.int64)
        sk["rtt_seq"] = -1
        sk["timer_on"] = False
        self._arm_timer(host, slot, now)
        self._kick(host, now)

    def _on_tcp_close(self, host, now, ev):
        slot = int(ev[P.SEQ])
        gen = int(ev[P.ACK])
        sk = host.socks[slot]
        if (sk["used"] and gen == sk["timer_gen"] and
                sk["state"] == TCPS_TIME_WAIT):
            self._sock_free(host, slot)

    # --- apps: UDP tier -----------------------------------------------------
    def _app(self, host, now, wake):
        # process routing mirror (engine.window._on_app): socket wakes
        # go to the socket's owner, slotless wakes to the SRC-stamped
        # process slot
        PP = len(host.app_rp)
        slot = int(wake[P.SEQ])
        if PP == 1:
            proc = 0
        else:
            proc = (self._rg(host, slot, "proc", 0) if slot >= 0
                    else int(wake[P.SRC]))
            proc = min(max(proc, 0), PP - 1)
        host.cur_proc = proc
        host.app_r = host.app_rp[proc]
        kind = int(self.hp_app_kind[host.hid, proc])
        if kind == APP_PING:
            self._app_ping(host, now, wake)
        elif kind == APP_PING_SERVER:
            self._app_ping_server(host, now, wake)
        elif kind == APP_PHOLD:
            self._app_phold(host, now, wake)
        elif kind == APP_GOSSIP:
            self._app_gossip(host, now, wake)
        elif kind == APP_BULK:
            self._app_bulk(host, now, wake)
        elif kind == APP_BULK_SERVER:
            self._app_bulk_server(host, now, wake)
        elif kind == APP_TGEN:
            self._app_tgen(host, now, wake)
        elif kind == APP_SOCKS_CLIENT:
            self._app_socks_client(host, now, wake)
        elif kind == APP_SOCKS_PROXY:
            self._app_socks_proxy(host, now, wake)
        host.cur_proc = 0                 # mirror app_proc reset
        host.app_r = host.app_rp[0]

    def _timer(self, host, t, aux=0):
        wake = np.zeros(P.PKT_WORDS, np.int32)
        wake[P.ACK] = WAKE_TIMER
        wake[P.SEQ] = -1
        wake[P.AUX] = np.int32(np.int64(aux) & 0xFFFFFFFF)
        wake[P.SRC] = host.cur_proc       # route back to this process
        self._q_push(host, t, EV_APP, wake)

    @staticmethod
    def _us31(t_ns):
        return (t_ns // SIMTIME_ONE_MICROSECOND) % (2**31)

    def _app_ping(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 2)
        if reason == WAKE_START:
            host.app_r[0] = self._udp_open(host)
            self._ping_send(host, now)
        elif reason == WAKE_TIMER:
            self._ping_send(host, now)
        else:  # echo
            rtt = (self._us31(now) - int(np.int64(wake[P.AUX]))) % (2**31)
            host.app_r[2] += 1
            self.stats[host.hid, defs.ST_RTT_SUM_US] += rtt
            self.stats[host.hid, defs.ST_RTT_COUNT] += 1
            self.stats[host.hid, defs.ST_XFER_DONE] += 1
            self._ns_observe(host.hid, self._ns.NS_RTT, rtt)
            self._ns_observe(host.hid, self._ns.NS_COMPLETION, rtt)
            limit = int(cfg[4])
            if limit > 0 and host.app_r[2] >= limit:
                self.stats[host.hid, defs.ST_APP_DONE] += 1

    def _ping_send(self, host, now):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        self._udp_sendto(host, now, host.app_r[0], cfg[0], cfg[1], cfg[3],
                         aux=self._us31(now))
        host.app_r[1] += 1
        limit = int(cfg[4])
        if limit == 0 or host.app_r[1] < limit:
            self._timer(host, now + int(cfg[2]))

    def _app_ping_server(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        if int(wake[P.ACK]) == WAKE_START:
            host.app_r[0] = self._udp_open(host, port=int(cfg[1]))
        elif int(wake[P.ACK]) == WAKE_SOCKET:
            self._udp_sendto(host, now, int(wake[P.SEQ]),
                             int(wake[P.SRC]), int(wake[P.SPORT]),
                             int(wake[P.LEN]), aux=int(wake[P.AUX]))

    def _exp_delay(self, host):
        u = self._draw(host)
        mean = jnp.float32(float(
            self.hp_app_cfg[host.hid, host.cur_proc][2]))
        d = int(jnp.maximum((-mean * jnp.log1p(-u)).astype(jnp.int64), 1))
        return d

    def _app_phold(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 2)
        if reason == WAKE_START:
            host.app_r[0] = self._udp_open(host, port=int(cfg[1]))
            n0 = min(max(int(cfg[4]), 0), host.qcap)
            for _ in range(n0):
                self._timer(host, now + self._exp_delay(host))
        elif reason == WAKE_TIMER:
            u = self._draw(host)
            n = int(cfg[0])
            peer = int(jnp.minimum((u * n).astype(jnp.int64), n - 1))
            if peer == host.hid:
                peer = (peer + 1) % n
            self._udp_sendto(host, now, host.app_r[0], peer, cfg[1], cfg[3])
            host.app_r[1] += 1
        else:
            self._timer(host, now + self._exp_delay(host))

    def _relay_gossip(self, host, now, height):
        """Mirror of apps.gossip._relay: always MAX_FANOUT (8) draws,
        identical float32 peer math, sends only the first `fanout`."""
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        n = max(int(cfg[0]), 2)
        k = min(max(int(cfg[2]), 0), 8)
        for j in range(8):
            u = self._draw(host)
            peer = int(jnp.minimum(
                (u * jnp.float32(n - 1)).astype(jnp.int64), n - 2))
            if peer >= host.hid:
                peer += 1
            if j < k:
                self._udp_sendto(host, now, host.app_r[0], peer,
                                 cfg[1], cfg[5], aux=height)

    def _app_gossip(self, host, now, wake):
        """Mirror of apps.gossip.app_gossip (block-gossip workload)."""
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 2)
        interval = int(cfg[3])
        if reason == WAKE_START:
            host.app_r[0] = self._udp_open(host, port=int(cfg[1]))
            host.app_r[5] = now
            if int(cfg[4]):
                self._timer(host, now + interval)
        elif reason == WAKE_TIMER:
            h = host.app_r[4] + 1
            host.app_r[4] = h
            host.app_r[1] = max(host.app_r[1], h)
            self._relay_gossip(host, now, h)
            self._timer(host, now + interval)
        else:
            h = int(np.int64(wake[P.AUX]))
            if h > host.app_r[1]:
                mined_at = host.app_r[5] + h * interval
                delay_us = max(now - mined_at, 0) // 1000
                host.app_r[1] = h
                host.app_r[2] += 1
                self.stats[host.hid, defs.ST_XFER_DONE] += 1
                self.stats[host.hid, defs.ST_RTT_SUM_US] += delay_us
                self.stats[host.hid, defs.ST_RTT_COUNT] += 1
                self._ns_observe(host.hid, self._ns.NS_RTT, delay_us)
                self._relay_gossip(host, now, h)

    # --- apps: TCP tier (apps.bulk / apps.tgen mirrors) ---------------------
    def _app_bulk(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 6)
        sock = _i32(host.app_r[0])
        if reason in (0, 1):        # start / timer -> (re)connect
            slot, _ok = self._tcp_connect(host, now, int(cfg[0]),
                                          int(cfg[1]))
            host.app_r[0] = slot
        elif reason == 3:           # connected
            self._tcp_write(host, now, sock, int(cfg[2]))
        elif reason == 6:           # sent: all bytes acked
            dur_us = max(now - self._rg(host, sock, "hs_time", 0), 0) \
                // 1000
            self._tcp_close_call(host, now, sock)
            host.app_r[1] += 1
            self.stats[host.hid, defs.ST_XFER_DONE] += 1
            self._ns_observe(host.hid, self._ns.NS_COMPLETION, dur_us)
            done = int(cfg[3]) > 0 and host.app_r[1] >= int(cfg[3])
            if done:
                self.stats[host.hid, defs.ST_APP_DONE] += 1
            else:
                self._timer(host, now + int(cfg[4]))

    def _app_bulk_server(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 6)
        slot = int(wake[P.SEQ])
        if reason == 0:
            lslot, _ok = self._tcp_listen(host, int(cfg[1]))
            host.app_r[0] = lslot
        elif reason == 5:           # accept: serve a GET-tagged SYN
            tag = self._rg(host, slot, "syn_tag", 0)
            fresh = int(wake[P.WND]) == self._rg(host, slot,
                                                 "timer_gen", 0)
            size = tag & ((1 << 30) - 1)
            if fresh and (tag & (1 << 30)) == 0 and size > 0:
                self._tcp_write(host, now, slot, size)
                self._tcp_close_call(host, now, slot)
        elif reason == 4:           # eof: inbound transfer done
            fresh = int(wake[P.WND]) == self._rg(host, slot,
                                                 "timer_gen", 0)
            tag = self._rg(host, slot, "syn_tag", 0)
            served_get = tag != 0 and (tag & (1 << 30)) == 0
            if fresh and not served_get:
                self._tcp_close_call(host, now, slot)
                self.stats[host.hid, defs.ST_XFER_DONE] += 1

    # --- socks proxy chains (apps.socks mirror) -----------------------------
    def _socks_rand_in(self, host, lo, hi, skip_self=False):
        """Mirror of apps.socks._rand_in: identical draw order and
        float32 index math."""
        u = self._draw(host)
        n = max(hi - lo, 1)
        if skip_self:
            in_pool = (lo <= host.hid < hi) and (n > 1)
            n_eff = n - (1 if in_pool else 0)
            idx = min(int(np.int64(u * np.float32(n_eff))), n_eff - 1)
            if in_pool and (lo + idx >= host.hid):
                idx += 1
            return lo + idx
        return lo + min(int(np.int64(u * np.float32(n))), n - 1)

    @staticmethod
    def _socks_pack_tag(target, size_u4k, hops=0):
        return (((hops & 0x3) << 29) | ((target & 0xFFFFF) << 9) |
                (size_u4k & 0x1FF))

    def _app_socks_client(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 6)
        slot = int(wake[P.SEQ])
        fresh = int(wake[P.WND]) == self._rg(host, slot, "timer_gen", 0)
        pause = int(cfg[7]) & ((1 << 56) - 1)
        hops = int(cfg[7]) >> 56

        if reason in (0, 1):            # start / timer -> fetch
            proxy = self._socks_rand_in(host, int(cfg[0]), int(cfg[1]))
            server = self._socks_rand_in(host, int(cfg[3]), int(cfg[4]))
            tag = self._socks_pack_tag(server, int(cfg[5]),
                                       max(hops - 1, 0))
            s, ok = self._tcp_connect(host, now, proxy, int(cfg[2]),
                                      tag=tag)
            host.app_r[0] = s
            host.app_r[2] = now
            if not ok:
                self._timer(host, now + pause)
        elif reason == 4:               # eof
            is_mine = fresh and slot == _i32(host.app_r[0])
            got_data = self._rg(host, slot, "rcv_nxt", 0) > 0
            if is_mine and got_data:
                delay_us = max(now - host.app_r[2], 0) // 1000
                self._tcp_close_call(host, now, slot)
                host.app_r[1] += 1
                self.stats[host.hid, defs.ST_XFER_DONE] += 1
                self.stats[host.hid, defs.ST_RTT_SUM_US] += delay_us
                self.stats[host.hid, defs.ST_RTT_COUNT] += 1
                self._ns_observe(host.hid, self._ns.NS_RTT, delay_us)
                self._ns_observe(host.hid, self._ns.NS_COMPLETION,
                                 delay_us)
                fin = int(cfg[6]) > 0 and host.app_r[1] >= int(cfg[6])
                if fin:
                    self.stats[host.hid, defs.ST_APP_DONE] += 1
                else:
                    self._timer(host, now + pause)
            elif is_mine:               # refused: zero bytes delivered
                self._tcp_close_call(host, now, slot)
                self._timer(host, now + pause)

    def _app_socks_proxy(self, host, now, wake):
        cfg = self.hp_app_cfg[host.hid, host.cur_proc]
        reason = min(max(int(wake[P.ACK]), 0), 6)
        slot = int(wake[P.SEQ])
        fresh = int(wake[P.WND]) == self._rg(host, slot, "timer_gen", 0)
        paired = self._rg(host, slot, "app_ref", 0)
        is_child = self._rg(host, slot, "parent", 0) >= 0

        if reason == 0:                 # start: listen
            lslot, ok = self._tcp_listen(host, int(cfg[1]))
            host.app_r[0] = (lslot + 1) if ok else 0
        elif reason == 5:               # accept: SOCKS CONNECT
            tag = self._rg(host, slot, "syn_tag", 0)
            hops = (tag >> 29) & 0x3
            target = (tag >> 9) & 0xFFFFF
            size = (tag & 0x1FF) << 12
            n_pool = int(cfg[4]) - int(cfg[3])
            self_in = int(cfg[3]) <= host.hid < int(cfg[4])
            has_pool = (n_pool > 1) or (n_pool == 1 and not self_in)
            extend = (hops > 0) and has_pool
            if (hops > 0) and not has_pool and fresh:
                self.stats[host.hid, defs.ST_CHAIN_SHORT] += 1
            if fresh:
                nxt = self._socks_rand_in(host, int(cfg[3]), int(cfg[4]),
                                          skip_self=True)
                dst = nxt if extend else target
                dport = int(cfg[1]) if extend else int(cfg[2])
                otag = (self._socks_pack_tag(target, tag & 0x1FF,
                                             hops - 1)
                        if extend else size)
                onward, ok = self._tcp_connect(host, now, dst, dport,
                                               tag=otag)
                if ok:
                    host.socks[onward]["app_ref"] = slot
                    host.socks[slot]["app_ref"] = onward
                else:
                    self._tcp_close_call(host, now, slot)
        elif reason == 2:               # data on the onward leg: relay
            relay = fresh and not is_child and paired >= 0
            ln = int(wake[P.LEN])
            if relay and ln > 0:
                self._tcp_write(host, now, paired, ln)
        elif reason == 4:               # eof: tear down the pair
            if fresh:
                if 0 <= slot < len(host.socks):
                    host.socks[slot]["app_ref"] = -1
                if 0 <= paired < len(host.socks):
                    host.socks[paired]["app_ref"] = -1
                self._tcp_close_call(host, now, slot)
                if paired >= 0:
                    self._tcp_close_call(host, now, paired)

    # --- tgen walk (apps.tgen mirror) ---------------------------------------
    def _rg(self, host, slot, key, default=0):
        """rget semantics: out-of-range slot reads as 0/False."""
        if 0 <= slot < len(host.socks):
            return host.socks[slot][key]
        return default

    def _tg_node(self, cur):
        return self.tg_nodes[min(max(int(cur), 0),
                                 self.tg_nodes.shape[0] - 1)]

    def _tg_exec_node(self, host, now, cur):
        """Mirror of tgen._exec_node. Returns proceed."""
        nd = self._tg_node(cur)
        kind = min(max(int(nd[TG.COL_KIND]), 0), 4)
        if kind == TG.NK_START:
            delay = int(nd[TG.COL_B])
            if delay > 0:
                self._timer(host, now + delay, aux=cur)
                return False
            return True
        if kind == TG.NK_TRANSFER:
            pcnt = max(int(nd[TG.COL_PCNT]), 1)
            u = self._draw(host)
            pick = int(nd[TG.COL_POFF]) + min(
                int(np.float32(u * np.float32(pcnt))), pcnt - 1)
            pick = min(max(pick, 0), self.tg_peers.shape[0] - 1)
            peer_host = int(self.tg_peers[pick, 0])
            peer_port = int(self.tg_peers[pick, 1])
            size = min(int(nd[TG.COL_B]), TG.TAG_SIZE_MASK)
            tag = size | (TG.TAG_PUT if int(nd[TG.COL_A]) == 1 else 0)
            slot, ok = self._tcp_connect(host, now, peer_host, peer_port,
                                         tag=tag)
            if ok:
                host.socks[slot]["app_ref"] = int(cur)
                self._tg_wd_arm(host, now, slot, 0, int(nd[TG.COL_C]),
                                int(nd[TG.COL_REF]))
            else:
                self._timer(host, now + SIMTIME_ONE_SECOND,
                            aux=-(int(cur) + 1))
            return False
        if kind == TG.NK_PAUSE:
            fixed = int(nd[TG.COL_A])
            if fixed < 0:
                u = self._draw(host)
                n = max(int(nd[TG.COL_C]), 1)
                at = int(nd[TG.COL_B]) + min(
                    int(np.float32(u * np.float32(n))), n - 1)
                t = int(self.tg_pool[min(max(at, 0),
                                         self.tg_pool.shape[0] - 1)])
            else:
                t = fixed
            if t > 0:
                self._timer(host, now + t, aux=cur)
                return False
            return True
        if kind == TG.NK_END:
            met = ((int(nd[TG.COL_A]) > 0 and
                    host.app_r[TG.REG_COUNT] >= int(nd[TG.COL_A])) or
                   (int(nd[TG.COL_B]) > 0 and
                    now - host.app_r[TG.REG_T0] >= int(nd[TG.COL_B])) or
                   (int(nd[TG.COL_C]) > 0 and
                    host.app_r[TG.REG_BYTES] >= int(nd[TG.COL_C])))
            if met:
                host.app_r[TG.REG_DONE] = 1
                self.stats[host.hid, defs.ST_APP_DONE] += 1
                return False
            return True
        # NK_SYNC
        ref = int(nd[TG.COL_REF])
        cnt = int(host.tgen_sync[ref]) + 1
        fire = cnt >= int(nd[TG.COL_A])
        host.tgen_sync[ref] = 0 if fire else cnt
        return fire

    def _tg_push_succs(self, host, stack, sp, cur):
        nd = self._tg_node(cur)
        eoff = int(nd[TG.COL_EOFF])
        ecnt = int(nd[TG.COL_ECNT])
        for j in range(ecnt):
            tgt = int(self.tg_edges[min(max(eoff + j, 0),
                                        self.tg_edges.shape[0] - 1)])
            if sp < TG.STACK_CAP:
                stack[sp] = tgt
                sp += 1
            else:
                self.stats[host.hid, defs.ST_TGEN_DROP] += 1
        return sp

    def _tg_walk(self, host, now, stack, sp):
        N = self.tg_nodes.shape[0]
        cap = 4 * N + 4 * TG.STACK_CAP
        it = 0
        while sp > 0 and it < cap:
            sp -= 1
            cur = stack[sp]
            if host.app_r[TG.REG_DONE] != 0:
                proceed = False
            else:
                proceed = self._tg_exec_node(host, now, cur)
            if proceed:
                sp = self._tg_push_succs(host, stack, sp, cur)
            it += 1
        self.stats[host.hid, defs.ST_TGEN_DROP] += sp

    def _tg_walk_enter(self, host, now, node):
        stack = [-1] * TG.STACK_CAP
        stack[0] = int(node)
        self._tg_walk(host, now, stack, 1)

    def _tg_walk_succ(self, host, now, node):
        stack = [-1] * TG.STACK_CAP
        sp = self._tg_push_succs(host, stack, 0, int(node))
        self._tg_walk(host, now, stack, sp)

    def _tg_wd_arm(self, host, now, slot, mark, timeout_ns, stallout_ns):
        sk = host.socks[slot]
        t_next = min(now + stallout_ns, sk["hs_time"] + timeout_ns)
        t_next = max(t_next, now + 1)
        w = np.zeros(P.PKT_WORDS, np.int32)
        w[P.ACK] = WAKE_TIMER
        w[P.SEQ] = slot
        w[P.AUX] = np.int32(TG.WD_AUX)
        w[P.WND] = sk["timer_gen"]
        w[P.LEN] = _i32(mark)
        self._q_push(host, t_next, EV_APP, w)

    def _tg_finish_transfer(self, host, now, sock):
        node = host.socks[sock]["app_ref"]
        nd = self._tg_node(node)
        dur_us = max(now - host.socks[sock]["hs_time"], 0) // 1000
        host.socks[sock]["app_ref"] = -1
        self._tcp_close_call(host, now, sock)
        host.app_r[TG.REG_COUNT] += 1
        host.app_r[TG.REG_BYTES] += int(nd[TG.COL_B])
        self.stats[host.hid, defs.ST_XFER_DONE] += 1
        self._ns_observe(host.hid, self._ns.NS_COMPLETION, dur_us)
        self._tg_walk_succ(host, now, node)

    def _app_tgen(self, host, now, wake):
        reason = min(max(int(wake[P.ACK]), 0), 6)
        slot = int(wake[P.SEQ])
        start_node = int(self.hp_app_cfg[host.hid, host.cur_proc][0])
        fresh = int(wake[P.WND]) == self._rg(host, slot, "timer_gen", 0)
        is_client = fresh and self._rg(host, slot, "app_ref", 0) >= 0

        if reason == 0:       # start
            nd = self._tg_node(start_node)
            if int(nd[TG.COL_A]) > 0:
                self._tcp_listen(host, int(nd[TG.COL_A]))
            host.app_r[TG.REG_T0] = now
            self._tg_walk_enter(host, now, start_node)
        elif reason == 1:     # timer (walk continuation or watchdog)
            aux = int(wake[P.AUX])
            if aux == TG.WD_AUX:
                node = self._rg(host, slot, "app_ref", 0)
                live = (fresh and node >= 0 and
                        self._rg(host, slot, "used", False))
                nd = self._tg_node(max(node, 0))
                metric = (self._rg(host, slot, "rcv_nxt", 0) +
                          self._rg(host, slot, "snd_una", 0))
                mark = int(wake[P.LEN])
                took = now >= (self._rg(host, slot, "hs_time", 0) +
                               int(nd[TG.COL_C]))
                stalled = metric == mark
                if live and (took or stalled):
                    host.socks[slot]["app_ref"] = -1
                    self.stats[host.hid, defs.ST_TGEN_ABORT] += 1
                    self._tcp_close_call(host, now, slot)
                    self._tg_walk_succ(host, now, node)
                elif live:
                    self._tg_wd_arm(host, now, slot, metric,
                                    int(nd[TG.COL_C]), int(nd[TG.COL_REF]))
            elif aux >= 0:
                self._tg_walk_succ(host, now, aux)
            else:
                self._tg_walk_enter(host, now, -aux - 1)
        elif reason == 3:     # connected
            tag = self._rg(host, slot, "syn_tag", 0)
            if (tag & TG.TAG_PUT) != 0 and is_client:
                self._tcp_write(host, now, slot, tag & TG.TAG_SIZE_MASK)
                self._tcp_close_call(host, now, slot)
        elif reason == 5:     # accept (server child established)
            tag = self._rg(host, slot, "syn_tag", 0)
            if fresh and (tag & TG.TAG_PUT) == 0:
                self._tcp_write(host, now, slot, tag & TG.TAG_SIZE_MASK)
                self._tcp_close_call(host, now, slot)
        elif reason == 4:     # eof
            if is_client:
                self._tg_finish_transfer(host, now, slot)
            else:
                is_put_child = (fresh and
                                self._rg(host, slot, "used", False) and
                                self._rg(host, slot, "parent", -1) >= 0 and
                                (self._rg(host, slot, "syn_tag", 0) &
                                 TG.TAG_PUT) != 0)
                if is_put_child:
                    self._tcp_close_call(host, now, slot)
                    self.stats[host.hid, defs.ST_XFER_DONE] += 1
        elif reason == 6:     # sent
            if is_client:
                self._tg_finish_transfer(host, now, slot)

    # --- exchange (identical math to engine.window.exchange) ---
    def _exchange(self):
        """Route/loss-roll/deliver this window's outboxes. Mirrors the
        round-3 deferral semantics: a destination takes at most
        min(incap, queue headroom) arrivals per window (headroom =
        free slots - reserve, floored at one arrival while at least
        two slots are free); the rest STAY in the source outbox with
        unchanged send
        times and re-exchange next window (ST_DEFER_FANIN). Returns
        the number of packets that departed an outbox (delivered or
        reliability-dropped) — the engines' shared progress signal."""
        all_pkts = []  # (global outbox order) host-major
        for host in self.hosts:
            for i, (stime, pkt) in enumerate(host.outbox):
                all_pkts.append([host.hid, i, stime, pkt, None, False])
        if not all_pkts:
            return 0
        delivered = {}  # dst -> list of entry refs, in source order
        departed = 0
        for ent in all_pkts:
            src, _i, stime, pkt = ent[0], ent[1], ent[2], ent[3]
            dst = min(max(int(pkt[P.DST]), 0), self.H - 1)
            sv, dv = self.hp_vertex[src], self.hp_vertex[dst]
            rel = np.float32(self.rel[sv, dv])
            lat = int(self.lat[sv, dv])
            arrival = stime + lat
            if int(pkt[P.FLAGS]) & P.F_SYN:
                # one-way latency stamp (engine.window.exchange)
                pkt = pkt.copy()
                pkt[P.SEQ] = _i32(lat // 1000)
                ent[3] = pkt
            ent[4] = arrival
            u = self._cheap_uniform(self._stream_of(R.DOMAIN_DROP, src),
                                    int(pkt[P.UID]))
            if rel > 0 and u <= rel:
                delivered.setdefault(dst, []).append(ent)
            else:
                self.stats[src, defs.ST_PKTS_DROP_NET] += 1
                ent[5] = True        # departed (lost on the wire)
                departed += 1
        for dst, lst in delivered.items():
            host = self.hosts[dst]
            nfree = len(host.free_slots)
            # progress floor admits one arrival only while a second
            # free slot remains for internal pushes (mirrors
            # engine.window._intake_take — THE intake policy)
            allow = min(self.cfg.incap,
                        max(nfree - self.reserve,
                            1 if nfree >= 2 else 0))
            for ent in lst[:allow]:
                slot = min(host.free_slots)
                host.free_slots.remove(slot)
                host.events[slot] = (ent[4], host.eq_ctr, EV_PKT,
                                     ent[3].copy())
                host.eq_ctr += 1
                ent[5] = True
                departed += 1
        # source-side carry: everything not departed stays, original
        # order; earliest carried arrival bounds the window advance
        for host in self.hosts:
            host.outbox = []
            host.ob_next = SIMTIME_MAX
        for ent in all_pkts:
            if not ent[5]:
                src = ent[0]
                host = self.hosts[src]
                host.outbox.append((ent[2], ent[3]))
                host.ob_next = min(host.ob_next, ent[4])
                self.stats[src, defs.ST_DEFER_FANIN] += 1
        return departed

    # --- lockstep pass recount (obs.passcope differential) ---
    def _exec_due(self, host, wend):
        """Execute the host's due minimum event plus the same-slot
        NIC-TX chain (engine.window._step_hot mirror). -> events run."""
        t, seq, kind, pkt = self._q_pop_min(host)
        self.stats[host.hid, defs.ST_EVENTS] += 1
        n = 1
        if kind == EV_APP:
            self._app(host, t, pkt)
        elif kind == EV_PKT:
            self._on_pkt(host, t, pkt)
        elif kind == EV_NIC_TX:
            self._on_tx(host, t, wend)
        elif kind == EV_TCP_TIMER:
            self._on_tcp_timer(host, t, pkt)
        elif kind == EV_TCP_CLOSE:
            self._on_tcp_close(host, t, pkt)
        if not self.cfg.cpu_model and host.events:
            slot = min(host.events, key=lambda s: (host.events[s][0],
                                                   host.events[s][1]))
            t2, _, k2, _ = host.events[slot]
            if t2 == t and k2 == EV_NIC_TX:
                self._q_pop_min(host)
                self.stats[host.hid, defs.ST_EVENTS] += 1
                self._on_tx(host, t, wend)
                n += 1
        return n

    def _drain_lockstep(self, wend):
        """Drain one window in the compiled engine's lockstep pass
        order, counting passes per rung label into self.pass_mix.

        Mirror of engine.window._drain_hot/_pass_hot: the same
        searchsorted rung selection over the same ladders, the same
        per-pass event budget (rung_batch events per gathered host on
        sparse rungs, one per ready host on dense), the same fixed
        active set inside a window rung with inner passes tallied into
        the w slot. State-identical to the plain per-host drain — hosts
        only interact at the exchange, and per-host event order is
        unchanged — but the pass counts line up with the device
        pass_acc so occupancy math can be recounted independently.
        -> events executed."""
        import bisect
        from .window import ladder_of, rung_batch, window_ladder
        cfg = self.cfg
        wks = window_ladder(cfg, self.H)
        ks = ladder_of(cfg, self.H)
        nexec = 0

        def run_pass(ready, batch):
            n = 0
            for host in ready:
                for _ in range(batch):
                    if self._next_time(host) >= wend:
                        break
                    n += self._exec_due(host, wend)
            return n

        active = [h for h in self.hosts if self._next_time(h) < wend]
        widx = bisect.bisect_left(wks, len(active))
        if wks and active and widx < len(wks):
            # window rung: the K-sub is gathered once at window open
            # (hosts idle at open stay out the whole window); each
            # inner pass reselects its own sub-ladder rung
            sub_ks = ladder_of(cfg, wks[widx])
            lbl = "w%d" % wks[widx]
            while True:
                ready = [h for h in active if self._next_time(h) < wend]
                if not ready:
                    break
                self.pass_mix[lbl] = self.pass_mix.get(lbl, 0) + 1
                r = bisect.bisect_left(sub_ks, len(ready))
                nexec += run_pass(ready, rung_batch(cfg, sub_ks[r])
                                  if r < len(sub_ks) else 1)
        else:
            while True:
                ready = [h for h in self.hosts
                         if self._next_time(h) < wend]
                if not ready:
                    break
                if wks:
                    # overflow past the window ladder runs plain dense
                    lbl, batch = "dense", 1
                else:
                    r = bisect.bisect_left(ks, len(ready))
                    sparse = r < len(ks)
                    lbl = "k%d" % ks[r] if sparse else "dense"
                    batch = rung_batch(cfg, ks[r]) if sparse else 1
                self.pass_mix[lbl] = self.pass_mix.get(lbl, 0) + 1
                nexec += run_pass(ready, batch)
        return nexec

    # --- main loop ---
    def run(self):
        from ..obs import metrics as MT
        from ..obs import trace as TR
        nt = min(self._next_time(h) for h in self.hosts)
        windows = 0
        ev0 = int(self.stats[:, defs.ST_EVENTS].sum())
        while nt < self.stop and nt < SIMTIME_MAX:
            if TR.ENABLED:
                _w_tok = TR.TRACER.begin("pyengine.window",
                                         sim_ns_start=int(nt))
            wend = min(nt + self.min_jump, self.stop)
            executed = False
            nexec = 0
            if self.count_passes:
                nexec = self._drain_lockstep(wend)
                executed = nexec > 0
            else:
                progressed = True
                while progressed:
                    progressed = False
                    for host in self.hosts:
                        while (host.events
                               and self._next_time(host) < wend):
                            t, seq, kind, pkt = self._q_pop_min(host)
                            self.stats[host.hid, defs.ST_EVENTS] += 1
                            nexec += 1
                            if kind == EV_APP:
                                self._app(host, t, pkt)
                            elif kind == EV_PKT:
                                self._on_pkt(host, t, pkt)
                            elif kind == EV_NIC_TX:
                                self._on_tx(host, t, wend)
                            elif kind == EV_TCP_TIMER:
                                self._on_tcp_timer(host, t, pkt)
                            elif kind == EV_TCP_CLOSE:
                                self._on_tcp_close(host, t, pkt)
                            progressed = True
                            executed = True
            shipped = self._exchange()
            windows += 1
            if TR.ENABLED:
                # the oracle's window loop on the same timeline as the
                # compiled engine's chunks: span per window (the
                # tracer's MAX_EVENTS cap bounds long runs)
                TR.TRACER.end(_w_tok, sim_ns_end=int(wend),
                              events=nexec, shipped=shipped)
            nt_eq = min(self._next_time(h) for h in self.hosts)
            if executed or shipped:
                # window-advance bound includes carried arrivals
                nt = min(nt_eq, min(h.ob_next for h in self.hosts))
            else:
                # anti-livelock (engine.window.win_body): advance to
                # the earliest queue event so jammed queues drain
                nt = nt_eq
        self.windows = windows
        if MT.ENABLED:
            reg = MT.REGISTRY
            reg.counter("pyengine.windows").inc(windows)
            reg.counter("pyengine.events").inc(
                int(self.stats[:, defs.ST_EVENTS].sum()) - ev0)
        return self.stats
