"""Simulation state: struct-of-arrays pytrees.

The reference keeps per-host state in heap objects (Host,
NetworkInterface, Socket/TCP, descriptor tables —
/root/reference/src/main/host/shd-host.c:64-130) and events as allocated
closures in per-host priority queues. On TPU the whole simulation is
three pytrees:

- :class:`Hosts` — every mutable per-host array, leading dim H. This is
  what the engine transforms (and donates between jit steps). Under
  ``vmap`` a "row" of it is one simulated host.
- :class:`HostParams` — read-only per-host configuration (topology
  vertex, bandwidths, app wiring).
- :class:`Shared` — replicated tables and scalars: the vertex-by-vertex
  latency/reliability oracle, RNG root, stop time, lookahead window.

Sizing knobs live in :class:`EngineConfig`; they are Python static so
XLA sees fixed shapes.
"""

from __future__ import annotations

from dataclasses import dataclass

import chex
import jax.numpy as jnp
import numpy as np

from ..core.simtime import SIMTIME_MAX
from ..core import constants as C
from ..net.packet import PKT_WORDS
from ..net.sack import K as SACK_K
from ..obs.netscope import NS_BUCKETS, NS_KINDS
from .defs import N_STATS


@dataclass(frozen=True)
class EngineConfig:
    """Static engine shape/size configuration."""
    num_hosts: int
    qcap: int = 32          # event-queue slots per host
    scap: int = 16          # socket table rows per host
    obcap: int = 32         # outbox (per-window emit budget) per host
    incap: int = 32         # per-window inbound packet budget per host
    txqcap: int = 16        # NIC transmit-ring slots per host
    chunk_windows: int = 64  # windows executed per jit invocation
    #   (larger chunks amortize dispatch + host sync; measured ~1.6x
    #   on-chip at 128 vs 32 — heartbeat/pcap/checkpoint granularity
    #   is per chunk, so not unbounded)
    cc_kind: int = 2        # 0=aimd 1=reno 2=cubic (reference default cubic)
    hostedcap: int = 1      # hosted-app wake-ring slots per host (hosting/)
    # Dead-branch pruning: which app kinds exist in this scenario, and
    # whether any host can open a TCP socket. The Simulation fills these
    # from the compiled process specs; the window program only traces
    # branches that can run — at 1 app kind the compile is a fraction
    # of the all-apps program (no behavioral effect: a pruned branch is
    # unreachable by construction).
    app_kinds: tuple = None  # e.g. (0, 3) — must include 0 (APP_NULL)
    uses_tcp: bool = True
    qdisc: int = 1          # NIC socket service: 0=fifo, 1=round-robin
    #   (reference --interface-qdisc, default fifo; rr kept as our
    #   default for fairness under many concurrent flows)
    cpu_model: bool = False  # host CPU delay model (net effect only
    #   when a scenario sets cpu costs; static so the default engine
    #   compiles none of it — the reference's is also off by default
    #   (--cpu-threshold [-1], shd-options.c:76)
    tracecap: int = 0       # packet-trace ring slots per host (obs.pcap;
    #   0 disables tracing entirely — the exchange compiles no trace code)
    synccap: int = 1        # tgen synchronize-barrier counters per host
    #   (sized by the Simulation to the compiled graphs' sync-node count)
    procs_per_host: int = 1  # process slots per host (the reference
    #   runs a process LIST per host, shd-configuration.h:36-95,
    #   slave_addNewVirtualProcess shd-slave.c:293 — e.g. tor + tgen
    #   on one machine). Each process has its own app kind/cfg/
    #   registers ([H, P] rows); sockets remember their owning process
    #   (sk_proc) so wakes route back to it. Sized by the Simulation
    #   to the scenario's max process count.
    exchange_a2a: bool = True  # sharded exchange protocol: bucketed
    #   ragged all-to-all (v2, per-shard wire bytes ~flat in shard
    #   count) vs the v1 all_gather (O(shards x outbox); set False to
    #   fall back). Single-chip runs ignore this.
    a2acap: int = 0         # all-to-all bucket slots per (src shard ->
    #   dst shard) pair; 0 = auto (4x the uniform-traffic share,
    #   clamped to the shard outbox). Bucket overflow DEFERS the tail
    #   at the source (counted in ST_DEFER_A2A; see
    #   parallel.shard.exchange_sharded).
    active_block: int = -1  # active-set compaction: a lockstep pass
    #   with few ready hosts gathers just those rows, steps them, and
    #   scatters back instead of paying a full all-hosts pass — the
    #   TPU-native analogue of the reference's host-steal load
    #   balancing (shd-scheduler-policy-host-steal.c:163-191): a
    #   single busy relay no longer charges every idle host one pass
    #   per event. -1 (default) = AUTO: a small rung ladder sized from
    #   num_hosts, each pass picking the smallest rung that fits its
    #   ready count (engine.window.ladder_of — replaces the round-3
    #   hand-tuned per-config constant): of 32, 256 and 512 rows, the
    #   smallest and the largest with 4K <= H. The 256 rung (1,024 <=
    #   H < 2,048) drains ONE event per gathered host
    #   (engine.window.rung_batch) and runs the wider passes too, 256
    #   rows at a time: a batch of 16 would step more lanes than the
    #   dense pass it replaces, one event keeps the dense pass
    #   schedule (pass count, windows and state unchanged), and a
    #   third copy of the handler machine would add about 32 MB of
    #   device code (hbm_peak_gb at socks-2k).
    #   Plus ONE window-level rung under the quarter rule 4K <= H
    #   (engine.window.window_ladder; round 9 tightened it from 2K <=
    #   H after the paired phold-4096 A/B showed the half-state [2048]
    #   window rung losing 1.2x to [512] — BASELINE.md round-9 table,
    #   tools/perf_ab.py). > 0 = one explicit rung of that size. 0 =
    #   off (always dense).
    #   Bit-identical in every mode: hosts only interact at window
    #   boundaries, so per-host (time, seq) execution order is
    #   unchanged.
    exsortcap: int = 0      # exchange sort-compaction cap: the window
    #   exchange's group-by-destination argsort ran over ALL H x obcap
    #   outbox slots (240k at socks10k — measured ~110 ms/window on
    #   chip, ~40% of the socks10k wall; TPU sorts are bitonic and
    #   expensive). When the window's surviving packet count fits this
    #   cap, the exchange first compacts the valid entries (stable,
    #   original order) and sorts only the cap-sized list; larger
    #   bursts fall back to the full sort. 0 = auto
    #   (engine.window.exsort_cap); bit-identical either way (a stable
    #   sort of the compacted subsequence equals the filtered stable
    #   sort of the full list).
    dstcap: int = 0         # destination-compaction cap for the
    #   arrival merge (engine.window.dst_cap): windows whose receiving
    #   host set fits the cap merge only those rows ([D] gathers
    #   instead of [H]-wide queue rewrites — the xplane trace showed
    #   the full-width merge's data-dependent gathers were ~45 ms of
    #   every socks10k window). 0 = auto (min(H, 4096) —
    #   engine.window.dst_cap); bit-identical either way (a no-arrival
    #   row's merge is the identity).
    event_batch: int = 16   # max consecutive due events drained per
    #   gathered host within ONE sparse compaction pass (engine.window.
    #   sparse_batch; forced to 1 under the CPU model and with hosted
    #   apps). Amortizes the rung gather/scatter over up to this many
    #   events — pass COUNT, not just pass cost, is the other factor
    #   of the lockstep-skew product (round-3 verdict item 2). Dense
    #   passes always drain exactly one event per ready host. Default
    #   widened 8 -> 16 by the paired event-batch A/B (BASELINE.md
    #   round-12 table; 32 was not significantly better and doubles
    #   the per-rung program size).
    hot_split: int = 1      # hot/cold state split in the lockstep
    #   drain: 1 (default) = the drain's gathers, scatters and
    #   while-loop carries move only hot_fields(cfg) — COLD_FIELDS
    #   plus the config-gated COLD_WHEN columns stay full-width at the
    #   window boundary and rejoin after the drain. 0 = carry the full
    #   pytree (the pre-split engine; kept for paired A/Bs and the
    #   split-equivalence tests). Bit-identical either way: the drain
    #   provably never touches cold columns (simlint STF303 statically;
    #   COLD_WHEN columns hold their alloc defaults in the gating
    #   configs, so the row prototype reads are exact — see row_proto).
    netscope: bool = False  # network observatory (obs.netscope):
    #   allocate the per-host latency histograms (ns_hist —
    #   [H, NS_KINDS, NS_BUCKETS] i64) and count RTT / completion /
    #   queue-delay / retransmit samples into them inside the jitted
    #   passes. Off (default) allocates the bucket axis at ZERO, so
    #   shapes, digests and checkpoints of existing runs are
    #   untouched and every observe() call is a static no-op.
    wide_state: int = 0     # at-rest state width (the shrink
    #   campaign, ROADMAP item 2): 0 (default) = the socket-table
    #   columns in NARROW_SPEC live at their narrow dtypes (i32/i16/
    #   i8/u16) with the SACK/OOO scoreboards delta-encoded relative
    #   to their stream anchors; every handler still computes at the
    #   canonical wide dtypes — widen_state/narrow_state convert at
    #   the drain's single row entry/exit (engine.window.step_one_host)
    #   and at the hosted op-replay boundary (hosting.bridge.apply_ops).
    #   1 = allocate full-width (the pre-shrink layout; the --wide-state
    #   A/B escape hatch, same pattern as hot_split=0). Bit-identical
    #   either way: NARROW_SPEC carries a machine-checked bound per
    #   column (simlint STF404) proving round-trip exactness, and
    #   digest chains canonicalize narrowed columns back to wide form
    #   before hashing (engine.window.canonicalize_state).


# Digest sections (obs.digest): Hosts field prefix -> the named state
# section a divergence report attributes to. Declared next to the
# arrays so a new field group gets a section in the same edit; fields
# matching no prefix digest under "other" (visible, never silently
# skipped).
STATE_SECTIONS = (
    ("eq_", "event_queue"),
    ("rng_ctr", "rng"),
    ("cpu_avail", "cpu"),
    ("nic_", "nic"),
    ("txq_", "nic"),
    ("pkt_ctr", "nic"),
    ("next_eport", "nic"),
    ("sk_", "tcp"),
    ("app_", "app"),
    ("tgen_sync", "app"),
    ("ob_", "outbox"),
    ("hw_", "hosted_wakes"),
    ("tr_", "trace_ring"),
    ("stats", "stats"),
    ("ns_", "netscope"),
    ("cap_peaks", "stats"),
)


def section_of(field: str, *, strict: bool = False) -> str:
    """Digest section for a Hosts field. With strict=True an unmapped
    field raises instead of landing in the "other" bucket — digest and
    checkpoint attribution silently degrade there, so simlint STF301
    (and tests/test_stateflow.py) require every field to be sectioned;
    the default stays lenient for forward-compat readers of old
    digest chains."""
    for prefix, section in STATE_SECTIONS:
        if field.startswith(prefix):
            return section
    if strict:
        raise KeyError(
            f"Hosts field {field!r} matches no STATE_SECTIONS prefix; "
            "add a (prefix, section) entry next to the field")
    return "other"


# Hot/cold column contract for the ROADMAP item-1 socket-table split
# (engine.window.drain_window actually enforces it at runtime: the
# drain's gathers, scatters and while-loop carries move hot columns
# only). Two levels:
#
# - COLD_FIELDS (static): a column NO drain-pass code touches in ANY
#   config — only read/written at window boundaries (exchange,
#   cap-peak sampling, window advance) or by host-side consumers
#   (pcap drain, reports). The stateflow analyzer (lint/stateflow.py,
#   STF303) verifies this against the drain-pass subgraph on every
#   simlint run, so a cold column cannot creep back into the working
#   set unnoticed; tools/state_matrix.py prints the measured matrix
#   this set was derived from.
# - COLD_WHEN (config-gated): columns whose drain accesses are
#   statically pruned under a named config predicate (cpu model off,
#   no hosted apps, no tgen, no TCP) — the socket table's SACK
#   bookkeeping, RTT/congestion state and per-connection config all
#   leave the working set on the UDP/phold tiers. See the invariant
#   note at COLD_WHEN below and docs/static-analysis.md.
COLD_FIELDS = frozenset({
    "ob_next",      # written by the exchange carry, read by advance
    "tr_time", "tr_pkt", "tr_dir", "tr_cnt", "tr_drop",  # pcap ring:
    #   exchange-side appends, host-side drain
    "cap_peaks",    # window-boundary sampling only
})

# The drain's STATIC hot working set: every Hosts column that is not
# in COLD_FIELDS, in declaration order. A LITERAL tuple on purpose —
# the stateflow analyzer reads it from the AST (never importing this
# module) and treats `hot_fields(cfg)` calls as exactly this set, so
# the drain's declared working set and the machine-checked one cannot
# drift. Import-time assert below pins HOT_FIELDS | COLD_FIELDS ==
# fields(Hosts) with no overlap; simlint STF300 re-checks it statically.
HOT_FIELDS = (
    "eq_time", "eq_seq", "eq_kind", "eq_pkt", "eq_ctr", "eq_next",
    "rng_ctr", "cpu_avail",
    "nic_busy", "nic_sched", "nic_rr", "nic_rx_until",
    "txq_pkt", "txq_head", "txq_cnt", "pkt_ctr", "next_eport",
    "sk_used", "sk_proto", "sk_state", "sk_lport", "sk_rport",
    "sk_rhost", "sk_parent", "sk_snd_una", "sk_snd_nxt", "sk_snd_max",
    "sk_snd_end", "sk_rcv_nxt", "sk_ooo_s", "sk_ooo_e", "sk_sack_s",
    "sk_sack_e", "sk_hole_end", "sk_rex_nxt", "sk_peer_fin",
    "sk_fin_acked", "sk_close_after", "sk_cwnd", "sk_ssthresh",
    "sk_srtt", "sk_rtt_min", "sk_rttvar", "sk_rto", "sk_rto_deadline",
    "sk_timer_on", "sk_timer_gen", "sk_dupacks", "sk_rtt_seq",
    "sk_rtt_time", "sk_ctl", "sk_peer_rwnd", "sk_sndbuf", "sk_rcvbuf",
    "sk_hs_time", "sk_last_tx", "sk_syn_tag", "sk_proc", "sk_app_ref",
    "sk_cc_wmax", "sk_cc_epoch", "sk_cc_k",
    "app_node", "app_r", "app_proc", "tgen_sync",
    "ob_pkt", "ob_time", "ob_cnt",
    "hw_time", "hw_pkt", "hw_cnt", "hw_drop",
    "stats", "ns_hist",
)

# Config-gated cold columns (the level-2 split): (guard, fields) —
# each field leaves the drain's RUNTIME working set when its guard
# holds for the engine config, because the static pruning already
# compiles no access to it (the Python `if cfg.*` branches and the
# app_kinds switch table). Exactness invariant (pinned by
# tests/test_compaction_invariants.py::
# test_hot_split_gating_bit_identical and the dual-run digest
# suite): under the guard, the column holds its
# alloc_hosts default on every row at every instant — the only
# reachable writes are the sock_alloc/sock_free resets, which write
# that same default — so the drain's compiled reads of it (e.g.
# tcp_want_tx scanning a TCP-less socket table) see the true value
# through the row prototype (row_proto), and discarding its writes is
# the identity. The stateflow gate cannot see static config, so a new
# access to one of these columns OUTSIDE its guard must be caught by
# the equivalence tests; grow this table only with the paired
# all-hot-vs-gated proof (docs/performance.md "hot/cold split").
COLD_WHEN = (
    # host CPU delay model off: cpu_avail is only touched inside
    # `if cfg.cpu_model:` blocks (engine.window.step_one_host)
    ("cpu_model_off", ("cpu_avail",)),
    # no hosted apps: the wake ring is appended only by
    # hosting.bridge (APP_HOSTED switch branch) and the mid-window
    # pause check compiles only when hostedcap > 1
    ("no_hosted", ("hw_time", "hw_pkt", "hw_cnt", "hw_drop")),
    # no tgen processes: the synchronize-barrier counters are touched
    # only by apps.tgen (APP_TGEN switch branch)
    ("no_tgen", ("tgen_sync",)),
    # no TCP sockets can exist (uses_tcp False prunes the rx TCP path
    # and the timer/close handlers; no TCP-capable app kind is
    # compiled): every column below is written only by the TCP
    # machine or reset-to-default by sock_alloc/sock_free, and every
    # residual compiled read (tcp_want_tx via nic.kick, the sock_alloc
    # TIME_WAIT eviction rank, the fifo qdisc key) sees the default —
    # the exact value the column invariantly holds. The UDP-touched
    # columns (sk_used/proto/lport/snd_end/rcv_nxt/timer_gen) and
    # sk_proc stay hot.
    ("no_tcp", (
        "sk_state", "sk_rport", "sk_rhost", "sk_parent", "sk_snd_una",
        "sk_snd_nxt", "sk_snd_max", "sk_ooo_s", "sk_ooo_e",
        "sk_sack_s", "sk_sack_e", "sk_hole_end", "sk_rex_nxt",
        "sk_peer_fin", "sk_fin_acked", "sk_close_after", "sk_cwnd",
        "sk_ssthresh", "sk_srtt", "sk_rtt_min", "sk_rttvar", "sk_rto",
        "sk_rto_deadline", "sk_timer_on", "sk_dupacks", "sk_rtt_seq",
        "sk_rtt_time", "sk_ctl", "sk_peer_rwnd", "sk_sndbuf",
        "sk_rcvbuf", "sk_hs_time", "sk_last_tx", "sk_syn_tag",
        "sk_app_ref", "sk_cc_wmax", "sk_cc_epoch", "sk_cc_k",
    )),
    # multi-process wake routing reads sk_proc (window._on_app, PP>1
    # branch); single-process no-TCP configs only ever write the
    # default 0 (sock_alloc stamps app_proc, which is 0 there)
    ("no_tcp_single_proc", ("sk_proc",)),
    # network observatory off: ns_hist is written only by
    # obs.netscope.observe, which is a static no-op when the bucket
    # axis is allocated at zero (cfg.netscope False) — the column is
    # then zero-size anyway, but gating it keeps the hot-column count
    # honest for the ledger's config_extras
    ("netscope_off", ("ns_hist",)),
)


def _guard_holds(guard: str, cfg: "EngineConfig") -> bool:
    def has_app(kind):
        # unknown app set (None = Simulation has not filled it) is
        # treated as "present": gating must be conservative
        return cfg.app_kinds is None or kind in cfg.app_kinds

    from ..apps.base import APP_HOSTED, APP_TGEN  # no import cycle:
    #   apps.base pulls engine.equeue/defs only

    no_hosted = cfg.hostedcap <= 1 and not has_app(APP_HOSTED)
    if guard == "cpu_model_off":
        return not cfg.cpu_model
    if guard == "no_hosted":
        return no_hosted
    if guard == "no_tgen":
        return not has_app(APP_TGEN)
    if guard == "no_tcp":
        return not cfg.uses_tcp and no_hosted
    if guard == "no_tcp_single_proc":
        return (not cfg.uses_tcp and no_hosted
                and cfg.procs_per_host <= 1)
    if guard == "netscope_off":
        return not cfg.netscope
    raise KeyError(f"unknown COLD_WHEN guard {guard!r}")


def hot_fields(cfg: "EngineConfig") -> tuple:
    """The drain's runtime hot working set for this config, in Hosts
    declaration order: HOT_FIELDS minus every COLD_WHEN column whose
    guard holds. With cfg.hot_split == 0 the full pytree (static cold
    columns included) is returned — the pre-split engine, for paired
    A/Bs and equivalence tests."""
    if not cfg.hot_split:
        return tuple(Hosts.__dataclass_fields__)
    off = set()
    for guard, fields in COLD_WHEN:
        if _guard_holds(guard, cfg):
            off.update(fields)
    return tuple(f for f in HOT_FIELDS if f not in off)


# At-rest narrow layout for provably-bounded socket columns (the
# shrink campaign, ROADMAP item 2). Each entry:
#
#   (field, wide, narrow, encoding, bound, why)
#
# - `wide` is the canonical COMPUTE dtype every handler sees (the
#   dtype the Hosts annotation comments declare and digest chains
#   canonicalize to);
# - `narrow` is the AT-REST dtype alloc_hosts uses when
#   cfg.wide_state == 0;
# - `encoding` is "abs" (plain cast — the value itself fits the
#   narrow dtype) or "rel:<anchor>" (stored as offset from the named
#   Hosts column; the free-slot sentinel -1 is preserved verbatim);
# - `bound` is the machine-checked maximum magnitude a live value can
#   take (plain int literal — the stateflow analyzer reads this table
#   from the AST and ast.literal_eval cannot fold shifts), and `why`
#   names the invariant that enforces it.
#
# Simlint STF404 verifies every entry (known dtypes, bound fits the
# narrow dtype, rel anchors are abs-narrowed i64 columns, non-empty
# why) and tests/test_shrink.py asserts the bounds against the
# documented max scenario parameters, failing by field name.
#
# Stream offsets are bounded by the TCP wire format: every SEQ/ACK is
# cast to i32 on the wire (net/tcp.py mk_segment), so an absolute
# stream offset past 2^31-1 would already corrupt the protocol — the
# sender's flow control (sndbuf/rwnd <= buf_cap = 2^30) never reaches
# it within any supported scenario envelope (max transfer ~2 GiB per
# connection; UDP's cumulative sk_rcv_nxt byte counter shares the
# same documented envelope). Scoreboard runs are additionally bounded
# by the receive/send buffer (< 2^30) so offsets relative to
# sk_rcv_nxt/sk_snd_una always fit i32 with room.
NARROW_SPEC = (
    # -- delta-encoded scoreboards (lever 1): [H, S, K] i64 -> i32 --
    ("sk_ooo_s", "i64", "i32", "rel:sk_rcv_nxt", 1073741824,
     "receiver OOO runs lie in (rcv_nxt, rcv_nxt + rcvbuf]; "
     "rcvbuf <= buf_cap = 2^30 (net/tcp.py _autotune)"),
    ("sk_ooo_e", "i64", "i32", "rel:sk_rcv_nxt", 1073741824,
     "run ends share the OOO window bound (end - rcv_nxt <= rcvbuf)"),
    ("sk_sack_s", "i64", "i32", "rel:sk_snd_una", 1073741824,
     "sender SACK runs lie in [snd_una, snd_una + sndbuf + rwnd); "
     "both <= buf_cap = 2^30 and runs are dropped below una on every "
     "ACK (net/tcp.py on_tcp_rx drop_below BEFORE the una write)"),
    ("sk_sack_e", "i64", "i32", "rel:sk_snd_una", 1073741824,
     "run ends share the SACK window bound"),
    # -- absolute stream offsets (lever 2): i64 -> i32 ----------------
    ("sk_snd_una", "i64", "i32", "abs", 2147483647,
     "wire i32 SEQ/ACK cast (net/tcp.py mk_segment) bounds every "
     "absolute stream offset below 2^31"),
    ("sk_snd_nxt", "i64", "i32", "abs", 2147483647, "wire i32 SEQ"),
    ("sk_snd_max", "i64", "i32", "abs", 2147483647, "wire i32 SEQ"),
    ("sk_snd_end", "i64", "i32", "abs", 2147483647,
     "app write cursor; flow control caps it at snd_una + sndbuf"),
    ("sk_rcv_nxt", "i64", "i32", "abs", 2147483647,
     "wire i32 ACK; UDP reuses it as a delivered-bytes counter under "
     "the same documented scenario envelope"),
    ("sk_hole_end", "i64", "i32", "abs", 2147483647,
     "recovery point: a snapshot of snd_max (wire-bounded)"),
    ("sk_rex_nxt", "i64", "i32", "abs", 2147483647,
     "retransmit cursor within [snd_una, snd_max]"),
    ("sk_peer_fin", "i64", "i32", "abs", 2147483647,
     "peer FIN stream offset (wire-bounded; -1 sentinel when unset)"),
    ("sk_rtt_seq", "i64", "i32", "abs", 2147483647,
     "RTT-sampled SEQ (wire-bounded; -1 sentinel between samples)"),
    # -- buffer/window sizes (lever 2): i64 -> i32 --------------------
    ("sk_peer_rwnd", "i64", "i32", "abs", 1073741824,
     "peer-advertised window, clamped to buf_cap = 2^30 on receive"),
    ("sk_sndbuf", "i64", "i32", "abs", 1073741824,
     "send buffer, autotuned within [min, buf_cap = 2^30]"),
    ("sk_rcvbuf", "i64", "i32", "abs", 1073741824,
     "receive buffer, autotuned within [min, buf_cap = 2^30]"),
    # -- small enums / flags / ports (lever 2) ------------------------
    ("sk_proto", "i32", "i8", "abs", 17,
     "IPPROTO id: 0 free, 1 hosted pipe, 6 tcp, 17 udp"),
    ("sk_state", "i32", "i8", "abs", 10,
     "TCPS_* enum, max TCPS_TIME_WAIT = 10 (net/socket.py)"),
    ("sk_ctl", "i32", "i8", "abs", 31,
     "pending-control bitmask SYN|SYNACK|ACKNOW|FIN|RST = 0x1f"),
    ("sk_lport", "i32", "u16", "abs", 65535,
     "port numbers <= MAX_PORT = 65535 (core/constants.py)"),
    ("sk_rport", "i32", "u16", "abs", 65535,
     "port numbers <= MAX_PORT = 65535"),
)

_DTYPES = {"i8": "int8", "i16": "int16", "u16": "uint16",
           "i32": "int32", "i64": "int64"}


def _narrow_maps():
    """(abs, rel) field maps parsed once from NARROW_SPEC: abs is
    {field: (wide_dt, narrow_dt)}, rel is {field: (wide_dt, narrow_dt,
    anchor)} with anchors resolvable through abs."""
    abs_f, rel_f = {}, {}
    for field, wide, narrow, enc, _bound, _why in NARROW_SPEC:
        wdt, ndt = _DTYPES[wide], _DTYPES[narrow]
        if enc == "abs":
            abs_f[field] = (wdt, ndt)
        else:
            rel_f[field] = (wdt, ndt, enc.split(":", 1)[1])
    return abs_f, rel_f


NARROW_ABS, NARROW_REL = _narrow_maps()
# the dtype probe the codec keys on: wide alloc gives int64 here
_PROBE_FIELD = "sk_snd_una"


def narrow_dtypes(cfg: "EngineConfig") -> dict:
    """{field: jnp dtype} for the at-rest layout this config allocates
    — empty when cfg.wide_state (the A/B escape hatch) asks for the
    full-width layout."""
    if getattr(cfg, "wide_state", 0):
        return {}
    out = {f: jnp.dtype(ndt) for f, (_w, ndt) in NARROW_ABS.items()}
    out.update({f: jnp.dtype(ndt)
                for f, (_w, ndt, _a) in NARROW_REL.items()})
    return out


def widen_state(t):
    """Decode a narrow at-rest Hosts tree (or a single vmapped row) to
    the canonical wide compute form -> (tree, was_narrow). Identity on
    wide state; `was_narrow` is a PYTHON bool read from static dtypes
    at trace time, so the wide path compiles zero conversion code.
    Rank-agnostic: scoreboard anchors broadcast over the trailing K
    axis via [..., None], so the same codec serves step_one_host's
    rows and apply_ops' full [H, S, K] tables."""
    probe = getattr(t, _PROBE_FIELD)
    if str(probe.dtype) == NARROW_ABS[_PROBE_FIELD][0]:
        return t, False
    reps = {}
    for f, (wdt, _ndt) in NARROW_ABS.items():
        reps[f] = getattr(t, f).astype(wdt)
    for f, (wdt, _ndt, anchor) in NARROW_REL.items():
        rel = getattr(t, f)
        anc = reps[anchor]  # anchors are abs-narrowed -> already wide
        reps[f] = jnp.where(rel >= 0,
                            rel.astype(wdt) + anc[..., None],
                            jnp.array(-1, wdt))
    return t.replace(**reps), True


def narrow_state(t):
    """Re-encode a wide Hosts tree (or row) to the narrow at-rest
    layout — the inverse of :func:`widen_state` (exact for every value
    within its NARROW_SPEC bound; free-slot -1 sentinels round-trip
    verbatim). Identity when the tree is already narrow."""
    probe = getattr(t, _PROBE_FIELD)
    if str(probe.dtype) != NARROW_ABS[_PROBE_FIELD][0]:
        return t
    reps = {}
    for f, (_wdt, ndt, anchor) in NARROW_REL.items():
        s = getattr(t, f)
        anc = getattr(t, anchor)  # still wide in t
        reps[f] = jnp.where(s >= 0, s - anc[..., None],
                            jnp.array(-1, s.dtype)).astype(ndt)
    for f, (_wdt, ndt) in NARROW_ABS.items():
        reps[f] = getattr(t, f).astype(ndt)
    return t.replace(**reps)


def shape_census(cfg: "EngineConfig") -> dict:
    """{field: (shape, dtype_name)} of every Hosts column at this
    config, via ``jax.eval_shape`` over the real :func:`alloc_hosts` —
    zero allocation, exact by construction. This is the ground truth
    the memory observatory's stdlib dims table
    (obs.memscope.HOSTS_DIMS — the jax-free byte census behind
    tools/state_matrix's bytes column and the capacity planner) is
    pinned against in tests/test_memscope.py: an alloc_hosts edit that
    forgets the table fails that pin by field name."""
    import jax

    sd = jax.eval_shape(lambda: alloc_hosts(cfg))
    return {f: (tuple(int(d) for d in getattr(sd, f).shape),
                str(getattr(sd, f).dtype))
            for f in sd.__dataclass_fields__}


def row_proto(cfg: "EngineConfig") -> "Hosts":
    """One host ROW of alloc_hosts defaults (no leading H axis) — the
    prototype the drain rebuilds its vmapped rows around: hot columns
    are replaced by the gathered data; cold columns ride as these
    defaults and are dropped on return (XLA dead-code-eliminates
    them), which is exact because a config-gated cold column's live
    value IS its default under the gating config (COLD_WHEN), and a
    static COLD_FIELDS column is never read by any handler (STF303)."""
    import dataclasses as _dc

    import jax

    h1 = alloc_hosts(_dc.replace(cfg, num_hosts=1))
    return jax.tree.map(lambda a: jnp.squeeze(a, 0), h1)


@chex.dataclass
class Hosts:
    """All mutable per-host state. Every leaf has leading dim H."""
    # --- event queue (the per-host scheduler) ---
    eq_time: jnp.ndarray   # [H, Q] i64, SIMTIME_MAX = free slot
    eq_seq: jnp.ndarray    # [H, Q] i32 tie-break (reference event_compare order)
    eq_kind: jnp.ndarray   # [H, Q] i32
    eq_pkt: jnp.ndarray    # [H, Q, PKT_WORDS] i32 payload
    eq_ctr: jnp.ndarray    # [H] i32 next sequence number
    eq_next: jnp.ndarray   # [H] i64 CACHED min(eq_time, axis=1) —
    #   maintained by every queue mutation (equeue.q_push/q_clear_slot,
    #   window.merge_arrivals) so the window loop's ready mask and
    #   min-next-event reductions read [H] instead of scanning the full
    #   [H, Q] table every lockstep pass (at 10k hosts x 192 slots that
    #   scan alone was ~15 MB of HBM traffic per pass, twice per pass)
    # --- per-host RNG use counter (key = fold_in(host_key, rng_ctr)) ---
    rng_ctr: jnp.ndarray   # [H] i32
    # --- CPU model (reference shd-cpu.c): busy horizon per host ---
    cpu_avail: jnp.ndarray  # [H] i64 time the CPU becomes available
    # --- NIC (reference shd-network-interface.c bandwidth accounting) ---
    nic_busy: jnp.ndarray      # [H] i64: tx free at this time
    nic_sched: jnp.ndarray     # [H] bool: an EV_NIC_TX event is in flight
    nic_rr: jnp.ndarray        # [H] i32: round-robin pointer over sockets
    nic_rx_until: jnp.ndarray  # [H] i64: rx engine busy horizon
    # NIC transmit ring: fully-formed packets awaiting bandwidth (the
    # analogue of socket output buffers + qdisc FIFO). UDP datagrams are
    # enqueued here at sendto time; TCP regenerates segments on pull.
    txq_pkt: jnp.ndarray       # [H, T, PKT_WORDS] i32
    txq_head: jnp.ndarray      # [H] i32 ring head
    txq_cnt: jnp.ndarray       # [H] i32 entries queued
    pkt_ctr: jnp.ndarray       # [H] i32: packets originated (drop RNG uid)
    next_eport: jnp.ndarray    # [H] i32: ephemeral port allocator cursor
    # --- socket table [H, S] ---
    sk_used: jnp.ndarray     # bool
    sk_proto: jnp.ndarray    # i32: 0 free, 6 tcp, 17 udp
    sk_state: jnp.ndarray    # i32 TCP state (net.tcp)
    sk_lport: jnp.ndarray    # i32 local port
    sk_rport: jnp.ndarray    # i32 remote port (0 = unconnected)
    sk_rhost: jnp.ndarray    # i32 remote host id (-1 = unconnected)
    sk_parent: jnp.ndarray   # i32 listener slot for accepted children (-1)
    sk_snd_una: jnp.ndarray  # i64 oldest unacked stream offset
    sk_snd_nxt: jnp.ndarray  # i64 next offset to transmit
    sk_snd_max: jnp.ndarray  # i64 highest offset ever transmitted
    sk_snd_end: jnp.ndarray  # i64 total bytes app has written
    sk_rcv_nxt: jnp.ndarray  # i64 next in-order offset expected
    # SACK scoreboard (the reference's shd-tcp-scoreboard.c as fixed
    # range sets, net.sack): K disjoint [start, end) ranges per socket
    sk_ooo_s: jnp.ndarray    # [H, S, K] i64 receiver out-of-order runs
    sk_ooo_e: jnp.ndarray    # [H, S, K] i64 (-1 = empty slot)
    sk_sack_s: jnp.ndarray   # [H, S, K] i64 sender: peer-sacked runs
    sk_sack_e: jnp.ndarray   # [H, S, K] i64 (accumulated from acks)
    sk_hole_end: jnp.ndarray  # i64 sender: recovery point — fast
    #   retransmission covers un-sacked bytes in [rex_nxt, hole_end)
    sk_rex_nxt: jnp.ndarray   # i64 sender: recovery cursor (skips
    #   sacked runs via the scoreboard)
    sk_peer_fin: jnp.ndarray  # i64 peer's FIN stream offset (-1 = none seen)
    sk_fin_acked: jnp.ndarray  # bool our FIN was acked
    sk_close_after: jnp.ndarray  # bool app closed: FIN after snd_end drains
    sk_cwnd: jnp.ndarray     # f32 congestion window (bytes)
    sk_ssthresh: jnp.ndarray  # f32
    sk_srtt: jnp.ndarray     # i64 (-1 until first sample; RFC6298)
    sk_rtt_min: jnp.ndarray  # i64 minimum RTT sample seen (-1 none) —
    #   the reference cubic's delayMin (shd-tcp-cubic.c:121-126),
    #   which bounds the growth-rate cap in net.congestion.on_ack
    sk_rttvar: jnp.ndarray   # i64
    sk_rto: jnp.ndarray      # i64 current retransmission timeout
    sk_rto_deadline: jnp.ndarray  # i64 desired timer expiration (0 = off)
    sk_timer_on: jnp.ndarray   # bool an EV_TCP_TIMER event is outstanding
    sk_timer_gen: jnp.ndarray  # i32 timer generation (stale-event filter)
    sk_dupacks: jnp.ndarray  # i32 duplicate-ack counter
    sk_rtt_seq: jnp.ndarray  # i64 offset being RTT-timed (-1 none; Karn)
    sk_rtt_time: jnp.ndarray  # i64 send time of the timed offset
    sk_ctl: jnp.ndarray      # i32 pending control bitmask (net.tcp CTL_*)
    sk_peer_rwnd: jnp.ndarray  # i64 peer advertised window
    sk_sndbuf: jnp.ndarray   # i64
    sk_rcvbuf: jnp.ndarray   # i64
    sk_hs_time: jnp.ndarray  # i64 handshake start (connect timeout/rtt)
    sk_last_tx: jnp.ndarray  # i64 last NIC service time (fifo qdisc key)
    sk_syn_tag: jnp.ndarray  # i32 connection-metadata tag carried on SYN
    sk_proc: jnp.ndarray     # i32 owning process slot (socket wakes
    #   route to this process's app — the analogue of the reference's
    #   descriptor-to-process ownership)
    sk_app_ref: jnp.ndarray  # i32 app-owner reference for client sockets
    #   (tgen: the behavior node whose transfer rides this socket; -1
    #   for server children and non-app sockets)
    # cubic congestion-control per-socket vars (net.congestion)
    sk_cc_wmax: jnp.ndarray   # f32 window before last loss
    sk_cc_epoch: jnp.ndarray  # i64 start of current cubic epoch (-1)
    sk_cc_k: jnp.ndarray      # f32 cubic K (seconds to plateau)
    # --- app layer (vectorized behavior machines; one row per
    # process slot) ---
    app_node: jnp.ndarray  # [H, PP] i32 behavior-graph node / phase
    app_r: jnp.ndarray     # [H, PP, 8] i64 app registers
    app_proc: jnp.ndarray  # [H] i32 process context during an EV_APP
    #   dispatch: pushes made by the running app (timers, socket
    #   allocations) are stamped with it so their wakes return to the
    #   same process; 0 between dispatches
    tgen_sync: jnp.ndarray  # [H, SY] i32 synchronize-barrier arrival counts
    # --- outbox: packets emitted this window awaiting exchange.
    # Packets the destination could not take this window (per-window
    # intake or queue headroom spent) STAY here and re-exchange next
    # window with unchanged send times — exact deferral, see
    # window.exchange ---
    ob_pkt: jnp.ndarray    # [H, O, PKT_WORDS] i32
    ob_time: jnp.ndarray   # [H, O] i64 send (wire-entry) time
    ob_cnt: jnp.ndarray    # [H] i32
    ob_next: jnp.ndarray   # [H] i64 earliest ARRIVAL time among carried
    #   packets (SIMTIME_MAX when none) — folded into the window-advance
    #   minimum so a deferred delivery reopens the window
    # --- hosted-app wake ring (hosting.bridge; drained per window) ---
    hw_time: jnp.ndarray   # [H, HW] i64 wake event times
    hw_pkt: jnp.ndarray    # [H, HW, PKT_WORDS] i32 wake payloads
    hw_cnt: jnp.ndarray    # [H] i32
    hw_drop: jnp.ndarray   # [H] i32 wakes lost to ring overflow
    # --- packet-trace ring (obs.pcap; drained per chunk) ---
    tr_time: jnp.ndarray   # [H, TC] i64
    tr_pkt: jnp.ndarray    # [H, TC, PKT_WORDS] i32
    tr_dir: jnp.ndarray    # [H, TC] i32: 0 rx, 1 tx
    tr_cnt: jnp.ndarray    # [H] i32
    tr_drop: jnp.ndarray   # [H] i32 records lost to ring overflow
    # --- observability ---
    stats: jnp.ndarray     # [H, N_STATS] i64
    ns_hist: jnp.ndarray   # [H, NSK, NSB] i64 network-observatory
    #   latency histograms (obs.netscope): per kind (RTT, completion,
    #   queue delay, retransmit interval), power-of-two µs buckets.
    #   NSB is NS_BUCKETS with cfg.netscope on, else ZERO — disabled
    #   runs keep their pre-netscope shapes and digests bit-for-bit.
    cap_peaks: jnp.ndarray  # [H, 4] i32 peak occupancy of the fixed
    #   capacity arrays (0=event queue, 1=socket table, 2=outbox,
    #   3=NIC tx ring) — the TPU analogue of the reference's
    #   ObjectCounter end-of-run report (shd-object-counter.c; there
    #   leaks are the hazard, here capacity headroom is)


@chex.dataclass
class HostParams:
    """Read-only per-host configuration, leading dim H."""
    hid: jnp.ndarray        # [H] i32 own host id (global, shard-invariant)
    rng_stream: jnp.ndarray  # [H] u32 per-host PRNG stream (core.rng)
    vertex: jnp.ndarray     # [H] i32 topology attachment
    bw_up: jnp.ndarray      # [H] i64 bytes/sec uplink
    bw_down: jnp.ndarray    # [H] i64 bytes/sec downlink
    app_kind: jnp.ndarray   # [H, PP] i32 app per process slot (apps
    #   registry; APP_NULL = empty slot)
    app_cfg: jnp.ndarray    # [H, PP, 8] i64 app static params
    nic_buf: jnp.ndarray    # [H] i64 NIC input buffer bytes
    cpu_cost: jnp.ndarray   # [H] i64 modeled CPU ns per executed event
    #   (= base event cost x frequencyRatio, precision-rounded at
    #   build; the modeled-app stand-in for shd-cpu.c's measured
    #   wallclock x ratio). 0 = free.
    cpu_threshold: jnp.ndarray  # [H] i64 blocked-CPU threshold (-1 off)
    rcvbuf0: jnp.ndarray    # [H] i64 explicit socket recv buffer, or -1
    #   = autotune from the delay-bandwidth product at establishment
    #   (reference <host socketrecvbuffer>, shd-tcp.c:340-433)
    sndbuf0: jnp.ndarray    # [H] i64 explicit send buffer, or -1
    pcap_on: jnp.ndarray    # [H] bool: record this host's packets
    #   (reference <host logpcap=...>, shd-network-interface.c:186-223)


@chex.dataclass
class Shared:
    """Replicated loop-invariant tables and scalars. The live window
    bounds [wstart, wend) are loop-carried scalars in engine.window, not
    stored here."""
    lat_ns: jnp.ndarray    # [V, V] i64 path latency
    rel: jnp.ndarray       # [V, V] f32 path reliability
    host_vertex: jnp.ndarray  # [H] i32 host -> topology vertex (replicated
    #   copy of HostParams.vertex: routing needs the vertex of REMOTE
    #   destination hosts, which a host-sharded table cannot provide)
    host_bw_up: jnp.ndarray    # [H] i64 replicated peer-bandwidth tables
    host_bw_down: jnp.ndarray  # [H] i64 (TCP buffer autotuning needs the
    #   REMOTE end's bandwidths, shd-tcp.c:386-404)
    rng_root: jnp.ndarray  # PRNG key (host-side / setup uses)
    seed32: jnp.ndarray    # u32 scalar: root of the cheap counter PRNG
    stop_time: jnp.ndarray  # i64 scalar
    min_jump: jnp.ndarray   # i64 scalar: lookahead window width
    # TCP tuning scalars (reference --tcp-congestion-control /
    # --tcp-windows / --tcp-ssthresh options, shd-options.c:132-133)
    cc_kind: jnp.ndarray       # i32: 0=aimd 1=reno 2=cubic
    tcp_init_wnd: jnp.ndarray  # f32 initial cwnd, packets (default 10)
    tcp_ssthresh0: jnp.ndarray  # f32 initial ssthresh (0 = discover)
    # tgen behavior-graph tables (apps.tgen; 1-row dummies when unused)
    tgen_nodes: jnp.ndarray    # [N, 10] i64 node table
    tgen_peers: jnp.ndarray    # [M, 2] i32 (host, port) pool
    tgen_pool: jnp.ndarray     # [K] i64 pause-choice pool (ns)
    tgen_edges: jnp.ndarray    # [E] i32 successor-node pool (multi-edge
    #   parallel walks: each node points at edges[eoff:eoff+ecnt])


def alloc_hosts(cfg: EngineConfig) -> Hosts:
    H, Q, S, O = cfg.num_hosts, cfg.qcap, cfg.scap, cfg.obcap
    T = cfg.txqcap

    def full(shape, val, dt):
        return jnp.full(shape, val, dtype=dt)

    # at-rest dtype per column: NARROW_SPEC's narrow dtype when the
    # shrink layout is on (cfg.wide_state == 0), else the wide dtype
    # named by the field's annotation comment. The stateflow model
    # intentionally keeps the WIDE dtype for these fields (handlers
    # only ever see widened rows — engine.window.step_one_host).
    _nd = narrow_dtypes(cfg)

    def ndt(name, wide):
        return _nd.get(name, wide)

    return Hosts(
        eq_time=full((H, Q), SIMTIME_MAX, jnp.int64),
        eq_seq=full((H, Q), 0, jnp.int32),
        eq_kind=full((H, Q), 0, jnp.int32),
        eq_pkt=full((H, Q, PKT_WORDS), 0, jnp.int32),
        eq_ctr=full((H,), 0, jnp.int32),
        eq_next=full((H,), SIMTIME_MAX, jnp.int64),
        rng_ctr=full((H,), 0, jnp.int32),
        cpu_avail=full((H,), 0, jnp.int64),
        nic_busy=full((H,), 0, jnp.int64),
        nic_sched=full((H,), False, jnp.bool_),
        nic_rr=full((H,), 0, jnp.int32),
        nic_rx_until=full((H,), 0, jnp.int64),
        txq_pkt=full((H, T, PKT_WORDS), 0, jnp.int32),
        txq_head=full((H,), 0, jnp.int32),
        txq_cnt=full((H,), 0, jnp.int32),
        pkt_ctr=full((H,), 0, jnp.int32),
        next_eport=full((H,), C.MIN_RANDOM_PORT, jnp.int32),
        sk_used=full((H, S), False, jnp.bool_),
        sk_proto=full((H, S), 0, ndt("sk_proto", jnp.int32)),
        sk_state=full((H, S), 0, ndt("sk_state", jnp.int32)),
        sk_lport=full((H, S), 0, ndt("sk_lport", jnp.int32)),
        sk_rport=full((H, S), 0, ndt("sk_rport", jnp.int32)),
        sk_rhost=full((H, S), -1, jnp.int32),
        sk_parent=full((H, S), -1, jnp.int32),
        sk_snd_una=full((H, S), 0, ndt("sk_snd_una", jnp.int64)),
        sk_snd_nxt=full((H, S), 0, ndt("sk_snd_nxt", jnp.int64)),
        sk_snd_max=full((H, S), 0, ndt("sk_snd_max", jnp.int64)),
        sk_snd_end=full((H, S), 0, ndt("sk_snd_end", jnp.int64)),
        sk_rcv_nxt=full((H, S), 0, ndt("sk_rcv_nxt", jnp.int64)),
        sk_ooo_s=full((H, S, SACK_K), -1, ndt("sk_ooo_s", jnp.int64)),
        sk_ooo_e=full((H, S, SACK_K), -1, ndt("sk_ooo_e", jnp.int64)),
        sk_sack_s=full((H, S, SACK_K), -1, ndt("sk_sack_s", jnp.int64)),
        sk_sack_e=full((H, S, SACK_K), -1, ndt("sk_sack_e", jnp.int64)),
        sk_hole_end=full((H, S), 0, ndt("sk_hole_end", jnp.int64)),
        sk_rex_nxt=full((H, S), 0, ndt("sk_rex_nxt", jnp.int64)),
        sk_peer_fin=full((H, S), -1, ndt("sk_peer_fin", jnp.int64)),
        sk_fin_acked=full((H, S), False, jnp.bool_),
        sk_close_after=full((H, S), False, jnp.bool_),
        sk_cwnd=full((H, S), 0.0, jnp.float32),
        sk_ssthresh=full((H, S), 0.0, jnp.float32),
        sk_srtt=full((H, S), -1, jnp.int64),
        sk_rtt_min=full((H, S), -1, jnp.int64),
        sk_rttvar=full((H, S), 0, jnp.int64),
        sk_rto=full((H, S), C.TCP_RTO_INIT, jnp.int64),
        sk_rto_deadline=full((H, S), 0, jnp.int64),
        sk_timer_on=full((H, S), False, jnp.bool_),
        sk_timer_gen=full((H, S), 0, jnp.int32),
        sk_dupacks=full((H, S), 0, jnp.int32),
        sk_rtt_seq=full((H, S), -1, ndt("sk_rtt_seq", jnp.int64)),
        sk_rtt_time=full((H, S), 0, jnp.int64),
        sk_ctl=full((H, S), 0, ndt("sk_ctl", jnp.int32)),
        sk_peer_rwnd=full((H, S), C.RECV_BUFFER_SIZE, ndt("sk_peer_rwnd", jnp.int64)),
        sk_sndbuf=full((H, S), C.SEND_BUFFER_SIZE, ndt("sk_sndbuf", jnp.int64)),
        sk_rcvbuf=full((H, S), C.RECV_BUFFER_SIZE, ndt("sk_rcvbuf", jnp.int64)),
        sk_hs_time=full((H, S), 0, jnp.int64),
        sk_last_tx=full((H, S), 0, jnp.int64),
        sk_syn_tag=full((H, S), 0, jnp.int32),
        sk_proc=full((H, S), 0, jnp.int32),
        sk_app_ref=full((H, S), -1, jnp.int32),
        sk_cc_wmax=full((H, S), 0.0, jnp.float32),
        sk_cc_epoch=full((H, S), -1, jnp.int64),
        sk_cc_k=full((H, S), 0.0, jnp.float32),
        app_node=full((H, max(cfg.procs_per_host, 1)), 0, jnp.int32),
        app_r=full((H, max(cfg.procs_per_host, 1), 8), 0, jnp.int64),
        app_proc=full((H,), 0, jnp.int32),
        tgen_sync=full((H, max(cfg.synccap, 1)), 0, jnp.int32),
        ob_pkt=full((H, O, PKT_WORDS), 0, jnp.int32),
        ob_time=full((H, O), 0, jnp.int64),
        ob_cnt=full((H,), 0, jnp.int32),
        ob_next=full((H,), SIMTIME_MAX, jnp.int64),
        hw_time=full((H, max(cfg.hostedcap, 1)), 0, jnp.int64),
        hw_pkt=full((H, max(cfg.hostedcap, 1), PKT_WORDS), 0, jnp.int32),
        hw_cnt=full((H,), 0, jnp.int32),
        hw_drop=full((H,), 0, jnp.int32),
        tr_time=full((H, max(cfg.tracecap, 1)), 0, jnp.int64),
        tr_pkt=full((H, max(cfg.tracecap, 1), PKT_WORDS), 0, jnp.int32),
        tr_dir=full((H, max(cfg.tracecap, 1)), 0, jnp.int32),
        tr_cnt=full((H,), 0, jnp.int32),
        tr_drop=full((H,), 0, jnp.int32),
        stats=full((H, N_STATS), 0, jnp.int64),
        ns_hist=full((H, NS_KINDS,
                      NS_BUCKETS if cfg.netscope else 0), 0,
                     jnp.int64),
        cap_peaks=full((H, 4), 0, jnp.int32),
    )


# Partition integrity: the declared hot/cold split covers every Hosts
# column exactly once, and every config-gated cold column is a member
# of the static hot set (it only LEAVES it under its guard). simlint
# STF300/STF304 re-check both statically on every lint run.
assert set(HOT_FIELDS).isdisjoint(COLD_FIELDS), \
    sorted(set(HOT_FIELDS) & COLD_FIELDS)
assert set(HOT_FIELDS) | COLD_FIELDS == set(Hosts.__dataclass_fields__), \
    sorted(set(Hosts.__dataclass_fields__)
           ^ (set(HOT_FIELDS) | COLD_FIELDS))
assert all(f in HOT_FIELDS for _, flds in COLD_WHEN for f in flds), \
    [f for _, flds in COLD_WHEN for f in flds if f not in HOT_FIELDS]


def make_shared(topo_lat_ns: np.ndarray, topo_rel: np.ndarray, rng_root,
                stop_time: int, min_jump: int, seed: int = 1,
                cc_kind: int = 2,
                tcp_init_wnd: float = 10.0,
                tcp_ssthresh0: float = 0.0,
                tgen_nodes: np.ndarray = None,
                tgen_peers: np.ndarray = None,
                tgen_pool: np.ndarray = None,
                tgen_edges: np.ndarray = None,
                host_vertex: np.ndarray = None,
                host_bw_up: np.ndarray = None,
                host_bw_down: np.ndarray = None) -> Shared:
    if host_vertex is None:
        host_vertex = np.zeros((1,), np.int32)
    if host_bw_up is None:
        host_bw_up = np.ones((1,), np.int64)
    if host_bw_down is None:
        host_bw_down = np.ones((1,), np.int64)
    if tgen_nodes is None:
        tgen_nodes = np.zeros((1, 10), np.int64)
    if tgen_peers is None:
        tgen_peers = np.zeros((1, 2), np.int32)
    if tgen_pool is None:
        tgen_pool = np.zeros((1,), np.int64)
    if tgen_edges is None:
        tgen_edges = np.full((1,), -1, np.int32)
    return Shared(
        lat_ns=jnp.asarray(topo_lat_ns, dtype=jnp.int64),
        rel=jnp.asarray(topo_rel, dtype=jnp.float32),
        host_vertex=jnp.asarray(host_vertex, dtype=jnp.int32),
        host_bw_up=jnp.asarray(host_bw_up, dtype=jnp.int64),
        host_bw_down=jnp.asarray(host_bw_down, dtype=jnp.int64),
        rng_root=rng_root,
        seed32=jnp.uint32(seed & 0xFFFFFFFF),
        stop_time=jnp.int64(stop_time),
        min_jump=jnp.int64(min_jump),
        cc_kind=jnp.int32(cc_kind),
        tcp_init_wnd=jnp.float32(tcp_init_wnd),
        tcp_ssthresh0=jnp.float32(tcp_ssthresh0),
        tgen_nodes=jnp.asarray(tgen_nodes, dtype=jnp.int64),
        tgen_peers=jnp.asarray(tgen_peers, dtype=jnp.int32),
        tgen_pool=jnp.asarray(tgen_pool, dtype=jnp.int64),
        tgen_edges=jnp.asarray(tgen_edges, dtype=jnp.int32),
    )
