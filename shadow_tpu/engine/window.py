"""The conservative-lookahead window loop.

This is the TPU redesign of the reference's round machinery: the
master/slave round loop (shd-slave.c:397-449, shd-master.c:410-440),
the scheduler barriers (shd-scheduler.c:602-635), the worker event loop
(shd-worker.c:123-190) and cross-host packet delivery
(shd-worker.c:216-271) — collapsed into three pure array programs:

1. `step_all_hosts`: every host pops and executes its earliest event if
   it falls inside the window — one lockstep iteration of the inner
   `lax.while_loop`, which runs until no host has a ready event. This
   replaces N worker threads walking per-host priority queues.
2. `exchange`: all packets emitted into per-host outboxes this window
   are routed (two [V,V] table gathers), loss-rolled (counter-based
   PRNG), grouped by destination and scattered into destination event
   queues. Cross-host arrivals always land at or after the window end
   because path latency >= the lookahead bound — the same causality
   argument as the reference's bump-to-barrier rule
   (shd-scheduler-policy-host-single.c:171-175).
3. `advance`: the global min next-event time (a jnp.min; a mesh-wide
   minimum when sharded, parallel.shard.global_min) opens the next window
   [t_min, t_min + min_jump) — exactly master_slaveFinishedCurrentRound.

`run_windows` stitches these into a device-resident multi-window loop so
one jit call executes many windows without host round-trips.
"""

from __future__ import annotations

from functools import partial

import chex
import jax
import jax.numpy as jnp

from ..core import rng as R
from ..core.rowops import radd, rget, rset
from ..core.simtime import SIMTIME_MAX
from ..net import nic
from ..net import packet as P
from ..net.socket import sock_demux
from ..net.tcp import on_tcp_timer, on_tcp_close, tcp_rx
from ..net.udp import udp_deliver
from ..apps.base import dispatch as app_dispatch
from . import equeue
from .defs import (EV_NULL, EV_APP, EV_PKT, EV_NIC_TX, EV_TCP_TIMER,
                   EV_TCP_CLOSE, ST_EVENTS, ST_PKTS_RECV, ST_PKTS_DROP_NET,
                   ST_PKTS_DROP_Q, ST_DEFER_FANIN)
from .state import (NARROW_ABS, NARROW_REL, EngineConfig, hot_fields,
                    narrow_state, row_proto, widen_state)


# --- Event handlers (row-level) -------------------------------------------
# Signature: handler(row, hp, sh, now, wend, pkt). `wend` is the current
# window bound so the NIC can defer work past the next exchange when its
# per-window emit budget is spent (overflow-to-next-window, never drop).

def _on_null(row, hp, sh, now, wend, pkt):
    return row


def _scoped(label, fn):
    """Stamp a handler with its stateflow entry name
    (lint/stateflow.py ENTRIES) via jax.named_scope, so the passcope
    observatory (obs/passcope.py) can attribute decoded HLO self-times
    back to the pass. Trace-time naming only — the compiled math, the
    shapes and the digest chain are untouched."""
    def h(*args):
        with jax.named_scope(label):
            return fn(*args)
    return h


def _make_handlers(cfg: EngineConfig):
    """Build the event-kind switch for this scenario. Static pruning:
    app kinds not present and (when uses_tcp is False) the whole TCP
    machine compile to nothing."""

    def _on_app(row, hp, sh, now, wend, pkt):
        # Multi-process routing (reference: process list per host,
        # shd-configuration.h:36-95): a wake belongs to the process
        # that owns its socket (sk_proc), or to the process stamped in
        # the SRC word for slotless timer/start wakes. The app then
        # runs against a single-process VIEW of the [P]-shaped app
        # state, so app code is process-count agnostic.
        PP = row.app_node.shape[0]
        if PP == 1:
            vrow = row.replace(app_node=row.app_node[0],
                               app_r=row.app_r[0])
            vhp = hp.replace(app_kind=hp.app_kind[0],
                             app_cfg=hp.app_cfg[0])
            vrow = app_dispatch(vrow, vhp, sh, now, pkt,
                                app_kinds=cfg.app_kinds)
            return vrow.replace(app_node=row.app_node.at[0].set(
                                    vrow.app_node),
                                app_r=row.app_r.at[0].set(vrow.app_r))
        slot = pkt[P.SEQ]
        proc = jnp.clip(jnp.where(slot >= 0, rget(row.sk_proc, slot),
                                  pkt[P.SRC]), 0, PP - 1)
        vrow = row.replace(app_node=rget(row.app_node, proc),
                           app_r=rget(row.app_r, proc),
                           app_proc=proc.astype(jnp.int32))
        vhp = hp.replace(app_kind=rget(hp.app_kind, proc),
                         app_cfg=rget(hp.app_cfg, proc))
        vrow = app_dispatch(vrow, vhp, sh, now, pkt,
                            app_kinds=cfg.app_kinds)
        return vrow.replace(
            app_node=rset(row.app_node, proc, vrow.app_node),
            app_r=rset(row.app_r, proc, vrow.app_r),
            app_proc=jnp.int32(0))

    def _on_pkt(row, hp, sh, now, wend, pkt):
        """Packet arrival at the NIC: admission, demux, protocol
        dispatch."""
        with jax.named_scope("nic.rx_admit"):
            row, keep = nic.rx_admit(row, hp, now, pkt)

        def deliver(r):
            r = r.replace(stats=r.stats.at[ST_PKTS_RECV].add(1))
            # delivery-status trail: admitted by the input buffer
            pkt_in = pkt.at[P.STATUS].set(pkt[P.STATUS] |
                                          P.DS_RX_BUFFERED)
            proto = pkt[P.FLAGS] & P.PROTO_MASK

            def tcp_path(rr):
                with jax.named_scope("tcp.rx"):
                    slot = sock_demux(rr, pkt[P.SRC], pkt[P.SPORT],
                                      pkt[P.DPORT], P.PROTO_TCP)
                    return jax.lax.cond(
                        slot >= 0,
                        lambda r2: tcp_rx(r2, hp, sh, now, slot, pkt_in),
                        lambda r2: r2, rr)

            def udp_path(rr):
                with jax.named_scope("udp.deliver"):
                    slot = sock_demux(rr, pkt[P.SRC], pkt[P.SPORT],
                                      pkt[P.DPORT], P.PROTO_UDP)
                    return jax.lax.cond(
                        slot >= 0,
                        lambda r2: udp_deliver(r2, hp, sh, now, slot,
                                               pkt_in),
                        lambda r2: r2, rr)

            if not cfg.uses_tcp:
                return udp_path(r)
            return jax.lax.cond(proto == P.PROTO_TCP, tcp_path, udp_path, r)

        return jax.lax.cond(keep, deliver, lambda r: r, row)

    def _on_tx(row, hp, sh, now, wend, pkt):
        with jax.named_scope("nic.tx"):
            return nic.on_tx(row, hp, sh, now, wend, pkt,
                             qdisc=cfg.qdisc)

    if cfg.uses_tcp:
        return [_on_null, _on_app, _on_pkt, _on_tx,
                _scoped("tcp.timer", on_tcp_timer), on_tcp_close]
    return [_on_null, _on_app, _on_pkt, _on_tx, _on_null, _on_null]


def step_one_host(row, hp, sh, wend, cfg: EngineConfig):
    """Pop and execute this host's earliest event if inside the window."""
    # At-rest narrow layout (state.NARROW_SPEC, cfg.wide_state == 0):
    # this is the drain's single codec insertion point — the row is
    # decoded to the canonical wide compute form here, every handler
    # below sees exactly the pre-shrink dtypes, and the single return
    # path re-encodes. `was_narrow` is a Python bool from static
    # dtypes, so a wide-state run compiles zero conversion code.
    row, was_narrow = widen_state(row)
    slot, t = equeue.q_min(row)
    ready = t < wend
    kind = jnp.where(ready, rget(row.eq_kind, slot), EV_NULL)
    pkt = rget(row.eq_pkt, slot)

    if cfg.cpu_model:
        # Reference CPU model (shd-cpu.c:55-107 + the blocked-I/O
        # check in event_execute, shd-event.c:52-81): when the CPU's
        # built-up delay exceeds the threshold, the event is pushed
        # forward to when the CPU drains instead of executing now.
        blocked = (ready & (hp.cpu_threshold >= 0) &
                   (row.cpu_avail - t > hp.cpu_threshold))
        retry_at = jnp.maximum(row.cpu_avail, t + 1)
        row = jax.lax.cond(
            blocked,
            lambda r: equeue.q_push(equeue.q_clear_slot(r, slot),
                                    retry_at, kind, pkt),
            lambda r: r, row)
        ready = ready & ~blocked
        kind = jnp.where(blocked, EV_NULL, kind)

    row = jax.lax.cond(ready, lambda r: equeue.q_clear_slot(r, slot),
                       lambda r: r, row)
    row = jax.lax.switch(kind, _make_handlers(cfg), row, hp, sh, t, wend, pkt)

    # Chain a due-now NIC-TX into the same lockstep pass: an app send
    # kicks an EV_NIC_TX at the current time when the NIC is idle, and
    # waiting a whole all-hosts pass to serve it doubles the pass count
    # of every send-heavy window. Executing the queue head early is
    # semantically identity (it would be the first pop of the next
    # pass) and the Python differential engine drains per-host queues
    # in exactly this order, so stats stay bit-identical. Disabled
    # under the CPU model: there every pop re-checks the blocked-CPU
    # threshold, which the chain would bypass.
    due = jnp.zeros((), jnp.bool_)
    if not cfg.cpu_model:
        slot2, t2 = equeue.q_min(row)
        due = ready & (t2 == t) & (rget(row.eq_kind, slot2) == EV_NIC_TX)
        with jax.named_scope("nic.tx"):
            row = jax.lax.cond(
                due,
                lambda r: nic.on_tx(equeue.q_clear_slot(r, slot2), hp,
                                    sh, t, wend, pkt, qdisc=cfg.qdisc),
                lambda r: r, row)

    if cfg.cpu_model:
        # charge this event's modeled CPU cost to the busy horizon
        row = row.replace(cpu_avail=jnp.where(
            ready,
            jnp.maximum(row.cpu_avail, t) + hp.cpu_cost,
            row.cpu_avail))

    row = row.replace(
        stats=radd(row.stats, ST_EVENTS,
                   jnp.where(ready, 1, 0) + jnp.where(due, 1, 0)))
    return narrow_state(row) if was_narrow else row


def step_all_hosts(hosts, hp, sh, wend, cfg: EngineConfig):
    # cfg is Python-static; close over it (vmap axes cover arrays only)
    def f(row, hprow):
        return step_one_host(row, hprow, sh, wend, cfg)

    return jax.vmap(f)(hosts, hp)


# --- Hot/cold state split (engine.state HOT_FIELDS / COLD_WHEN) -----------
#
# The drain below never moves the full Hosts pytree: drain_window
# splits it ONCE into the config's hot working set (a dict of hot
# columns) and leaves everything else untouched at full width, then
# rejoins at the window boundary. All gathers, scatters and while-loop
# carries inside operate on the hot dict only — previously every
# window-rung gather and every per-pass sub-compaction hauled all 81
# columns (cold SACK bookkeeping, trace rings, stats sampling
# included) through HBM once per pass. The vmapped row is rebuilt
# around the static row prototype (row_proto): cold columns ride as
# their config-invariant defaults and are dropped on return, so XLA
# dead-code-eliminates them from the compiled pass.

def _split_hosts(hosts, names):
    """Hosts -> {field: array} for the hot working set."""
    return {f: getattr(hosts, f) for f in names}


def _join_hosts(hosts, hot, names):
    """Rejoin the drained hot columns into the full pytree (cold
    columns pass through untouched — byte-identical by contract)."""
    return hosts.replace(**{f: hot[f] for f in names})


def _step_hot(hot, proto, hp, sh, wend, cfg: EngineConfig, names):
    """step_all_hosts over the hot working set only."""
    def f(hrow, hprow):
        row = proto.replace(**{f2: hrow[f2] for f2 in names})
        row = step_one_host(row, hprow, sh, wend, cfg)
        return {f2: getattr(row, f2) for f2 in names}

    return jax.vmap(f)(hot, hp)


# AUTO per-pass rung candidates (ladder_of), and the one of them that
# drains a single event per gathered host (rung_batch)
_AUTO_RUNGS = (32, 256, 512)
_ONE_EVENT_RUNG = 256


def ladder_of(cfg: EngineConfig, H: int = None):
    """Active-set compaction rung sizes for this config (ascending),
    WITHOUT the implicit dense fallback rung.

    - active_block > 0: one explicit rung (the round-3 hand-tuned
      knob, kept for A/B tests and overrides).
    - active_block == 0: compaction off — always dense (the round-3
      default, kept so dense-vs-sparse equality tests stay meaningful).
    - active_block == -1 (default): AUTO — a small ladder of rungs
      sized to the host count; each pass picks the smallest rung that
      fits its ready count, so the hand-tuned per-config constant the
      round-3 verdict flagged is gone (the reference's host-steal load
      balancing needed no tuning either,
      shd-scheduler-policy-host-steal.c:266-299). Candidates 32, 256
      and 512 must satisfy 4*K <= H: gathering more than a quarter of
      the rows costs close to a dense pass (round-3 block-size sweep,
      git 9b878c3). The smallest and the largest that fit are kept, so
      at most two sparse rungs (each compiles another copy of the
      handler machine): [32] for 128 <= H < 1,024, [32, 256] for
      1,024 <= H < 2,048, [32, 512] from 2,048. The 256 rung drains
      one event per host per pass (rung_batch), and runs the passes too
      wide for it as well, in chunks of 256 rows (_pass_hot).
    """
    if H is None:
        H = cfg.num_hosts
    if cfg.active_block > 0:
        return [min(cfg.active_block, H)]
    if cfg.active_block == 0:
        return []
    fit = [k for k in _AUTO_RUNGS if 4 * k <= H]
    return sorted({fit[0], fit[-1]}) if fit else []


def rung_batch(cfg: EngineConfig, K: int) -> int:
    """Events drained per gathered host in one pass of the per-pass
    rung of K rows (ladder_of): sparse_batch(cfg), except AUTO's 256
    rung, which drains one. That rung stands where the ladder would
    jump from 32 rows to a dense pass over fewer than 2,048: 256 rows
    times a batch of 16 is 4,096 lane-steps, more than the dense pass
    it replaces, while one event per ready host is exactly the dense
    pass's schedule (the same passes, windows and state)."""
    if cfg.active_block == -1 and K == _ONE_EVENT_RUNG:
        return 1
    return sparse_batch(cfg)


def sparse_batch(cfg: EngineConfig) -> int:
    """Events executed per gathered host per sparse pass (the inner
    bounded drain). 1 under the CPU model (every pop must re-check the
    blocked-CPU threshold against the busy horizon accumulated by the
    PREVIOUS pop — batching would reorder those checks) and with
    hosted apps (the wake-ring pause margin in run_windows assumes at
    most ~1 wake per host per pass)."""
    if cfg.cpu_model or cfg.hostedcap > 1:
        return 1
    return cfg.event_batch


def window_ladder(cfg: EngineConfig, H: int = None):
    """Window-level active-set rung sizes (ascending), without the
    dense fallback. The set of hosts that can execute ANY event inside
    a window is fixed at window open: hosts interact only at window
    boundaries, and a host's own handlers can only schedule events for
    itself (loopback included), so a host whose earliest event lies at
    or past wend stays idle for the WHOLE window. That makes a single
    gather-at-window-open / scatter-at-window-close exact — the inner
    drain loop then runs every pass on [K] rows with no per-pass
    gather, scatter, or full-state switch carry (measured ~37 ms of
    every socks10k window, tools/xplane_profile.py round 4).

    Disabled (empty) with hosted apps: the mid-window wake-ring pause
    check needs the full host set.
    """
    if H is None:
        H = cfg.num_hosts
    if cfg.hostedcap > 1 or cfg.active_block == 0:
        return []
    if cfg.active_block > 0:
        return [min(cfg.active_block, H)]
    # ONE auto rung: the largest candidate with 4K <= H — the same
    # quarter rule as the per-pass ladder (ladder_of): gathering more
    # than a quarter of the rows costs close to a dense pass. The
    # round-4..8 rule here was the looser 2K <= H, which at H=4096
    # picked a [2048] rung — HALF the state gathered per window — and
    # is the measured phold-4096 regression suspect: the round-9
    # paired A/B (tools/perf_ab.py, BASELINE.md round-9 table;
    # platform cpu) has active_block=512 beating the 2048-rung AUTO
    # 1.21-1.25x in EVERY paired rep at identical pass counts, so the
    # quarter rule now picks 512 there (same pass mix as the winning
    # variant). At the at-scale shapes nothing changes: H >= 8192
    # still selects the 2048 rung socks10k/tor50k were measured with.
    # Only one rung either way: a window rung pays its gather once
    # for the whole window and the inner drain re-compacts per pass
    # (drain_window), so finer window rungs buy almost nothing —
    # while every extra rung compiles another full copy of the
    # event-handler machine (measured: the 3-rung nested build took
    # ~29 min of XLA compile; program size, not run time, is the
    # binding cost of extra rungs)
    for k in (2048, 512):
        if 4 * k <= H:
            return [k]
    return []


def drain_window(hosts, hp, sh, wend, cfg: EngineConfig, pc):
    """Execute every event below `wend` (one whole window's pass
    loop), window-level active-set compaction applied when the active
    count fits a rung. Returns (hosts, pc) with pass counters
    accumulated per rung (window rungs first, then the per-pass rungs
    of the dense fallback, then dense — see pass_labels).

    Hot/cold split (state.HOT_FIELDS/COLD_WHEN): the full pytree is
    split here ONCE per window; everything inside — the rung gathers,
    per-pass sub-compaction and both while-loop carries — moves the
    hot working set only, and the cold columns rejoin untouched at
    the return. cfg.hot_split=0 restores the full-tree carry."""
    names = hot_fields(cfg)
    proto = row_proto(cfg)
    hot = _split_hosts(hosts, names)
    hot, pc = _drain_hot(hot, proto, hp, sh, wend, cfg, pc, names)
    return _join_hosts(hosts, hot, names), pc


def _drain_hot(hot, proto, hp, sh, wend, cfg: EngineConfig, pc, names):
    H = hot["eq_ctr"].shape[0]
    wks = window_ladder(cfg, H)
    nw = len(wks)

    def fallback(h, pc2):
        # full-set drain. With a window rung present this branch only
        # runs population-wave windows (most hosts active), where the
        # dense step is the right tool anyway — so it compiles the
        # plain dense loop, not another rung-ladder copy of the
        # handler machine. Without window rungs (small/mid H, hosted
        # apps, explicit active_block) it IS the engine, and the
        # per-pass ladder applies as before (_pass_hot handles the
        # ladderless active_block=0 case as plain dense).
        use_ladder = not wks

        def ev_cond(carry2):
            h2, _ = carry2
            go = jnp.min(h2["eq_next"]) < wend
            if cfg.hostedcap > 1:
                # pause before a hosted wake ring can overflow so the
                # CPU tier drains mid-window (the window re-opens on
                # the next call). The threshold floor keeps tiny
                # manual hostedcap values from wedging the loop.
                # (hw_* are pinned hot whenever hostedcap > 1 —
                # COLD_WHEN "no_hosted".)
                cap = h2["hw_time"].shape[1]
                go = go & (jnp.max(h2["hw_cnt"]) < max(cap - 4, 1))
            return go

        def ev_body(carry2):
            h2, pc3 = carry2
            if use_ladder:
                h2, rung = _pass_hot(h2, proto, hp, sh, wend, cfg,
                                     names)
            else:
                with jax.named_scope("dense"):
                    h2 = _step_hot(h2, proto, hp, sh, wend, cfg,
                                   names)
                rung = len(ladder_of(cfg, H))  # the dense slot
            return h2, pc3.at[nw + rung].add(1)

        return jax.lax.while_loop(ev_cond, ev_body, (h, pc2))

    if not wks:
        return fallback(hot, pc)

    active = hot["eq_next"] < wend                    # [H]
    nact = jnp.sum(active, dtype=jnp.int32)

    def make_win(K, slot):
        def f(h, pc2):
            rank = jnp.cumsum(active) - 1
            take = active & (rank < K)
            tgt = jnp.where(take, rank, K).astype(jnp.int32)
            hid = jnp.arange(H, dtype=jnp.int32)
            dummy = jnp.argmin(active).astype(jnp.int32)
            idx = jnp.full((K,), dummy, jnp.int32).at[tgt].set(
                hid, mode="drop")
            sub = {f2: h[f2][idx] for f2 in names}
            shp = jax.tree.map(lambda a: a[idx], hp)

            def c(carry2):
                s, _ = carry2
                return jnp.min(s["eq_next"]) < wend

            def b(carry2):
                # per-pass sub-compaction INSIDE the gathered set:
                # early passes run dense over [K], but once the easy
                # hosts drain, the remaining passes (the busiest
                # host's long tail) gather [32]-row subsets of the
                # sub — without this, every tail pass pays the full
                # [K]-row switch (measured: a flat [2048]-wide drain
                # was SLOWER than the per-pass ladder it replaced)
                s, n = carry2
                s, _rung = _pass_hot(s, proto, shp, sh, wend, cfg,
                                     names)
                return s, n + 1

            sub, n = jax.lax.while_loop(c, b, (sub, jnp.int64(0)))
            h = {f2: h[f2].at[idx].set(sub[f2]) for f2 in names}
            return h, pc2.at[slot].add(n)
        return _scoped(f"w{K}", f)

    branches = [make_win(K, i) for i, K in enumerate(wks)] + [fallback]
    rung = jnp.searchsorted(jnp.asarray(wks, jnp.int32), nact,
                            side="left").astype(jnp.int32)
    # arrival-only windows (every queue event at/past wend; the window
    # opened on a carried ob_next arrival) execute nothing — route
    # them to the fallback, whose loop exits without the K-row
    # gather/scatter a window rung would pay for zero passes
    rung = jnp.where(nact == 0, jnp.int32(len(wks)), rung)
    return jax.lax.switch(rung, branches, hot, pc)


def pass_labels(cfg: EngineConfig, H: int = None):
    """Cost-model labels/sizes for drain_window's pass counters:
    window rungs, then the dense-fallback's per-pass rungs, then
    dense. Where AUTO's one-event 256 rung tops the ladder, the dense
    slot counts the passes it runs in chunks (_pass_hot); they are
    charged H lane-steps, an upper bound but where a pass needs more
    than H / 256 chunks."""
    if H is None:
        H = cfg.num_hosts
    wks = window_ladder(cfg, H)
    ks = ladder_of(cfg, H)
    return ([(f"w{k}", k) for k in wks] +
            [(f"k{k}", k) for k in ks] + [("dense", H)])


def pass_batches(cfg: EngineConfig, H: int = None):
    """{pass label: events per lane per counted pass} for pass_labels:
    a window rung's pass sparse_batch(cfg) (its inner passes are not
    counted apart), a per-pass rung rung_batch, dense one. The one
    table the occupancy and cost-model readers charge lane-steps by."""
    if H is None:
        H = cfg.num_hosts
    out = {f"w{k}": sparse_batch(cfg) for k in window_ladder(cfg, H)}
    out.update({f"k{k}": rung_batch(cfg, k) for k in ladder_of(cfg, H)})
    out["dense"] = 1
    return out


def step_window_pass(hosts, hp, sh, wend, cfg: EngineConfig):
    """One lockstep pass with active-set compaction.

    The dense pass pays O(H x row-state) per iteration even when one
    busy host is the only one with events left in the window — the
    lockstep-skew cost that made at-scale TCP runs follow the busiest
    relay (the round-2 diagnosis; the reference solves the same skew by
    migrating hosts between threads, shd-scheduler-policy-host-steal.c:
    163-191,266-299). Here: count the ready hosts, pick the smallest
    ladder rung K >= nready, gather exactly those rows, drain up to
    rung_batch(cfg, K) consecutive due events per gathered host,
    scatter back — O(K x row-state) amortized over up to B events
    (a one-event rung keeps the dense pass schedule) — else fall
    back to the dense all-hosts step (which executes one event on EVERY
    ready host, so it is strictly better when most hosts are busy).

    Exactness: hosts interact only at window boundaries (loopback
    delivery is host-local), so any per-pass subset schedule that
    steps each host's own events in (time, seq) order produces
    bit-identical state — including draining SEVERAL consecutive due
    events for one host in a single pass (that is exactly the order
    the per-host queue would pop them over consecutive passes, and the
    order the pyengine oracle drains them in). A not-ready row's step
    is the identity (every handler is gated on `ready`; pinned by
    tests/test_compaction_invariants.py::test_idle_step_identity),
    which makes dummy gather slots (duplicates of one not-ready host)
    harmless: every duplicate scatter-back writes identical bytes.

    Returns (hosts, rung) where rung indexes ladder_of(cfg) with
    len(ladder) == the dense fallback (pass-mix accounting for the
    SimReport cost model).

    Public full-tree wrapper (tests, tools/phase_profile.py); the
    drain itself calls the hot-working-set core `_pass_hot` directly.
    """
    names = hot_fields(cfg)
    hot = _split_hosts(hosts, names)
    hot, rung = _pass_hot(hot, row_proto(cfg), hp, sh, wend, cfg,
                          names)
    return _join_hosts(hosts, hot, names), rung


def _pass_hot(hot, proto, hp, sh, wend, cfg: EngineConfig, names):
    H = hot["eq_ctr"].shape[0]
    ks = ladder_of(cfg, H)
    ready = hot["eq_next"] < wend                     # [H]
    nready = jnp.sum(ready, dtype=jnp.int32)

    def dense(h):
        with jax.named_scope("dense"):
            return _step_hot(h, proto, hp, sh, wend, cfg, names)

    def make_sparse(K):
        def sparse(h):
            rank = jnp.cumsum(ready) - 1
            take = ready & (rank < K)
            tgt = jnp.where(take, rank, K).astype(jnp.int32)
            hid = jnp.arange(H, dtype=jnp.int32)
            # dummy slots point at the first NOT-ready host: whenever a
            # dummy is needed (nready < K), one exists (nready < H), and
            # its step is the identity (see docstring)
            dummy = jnp.argmin(ready).astype(jnp.int32)
            idx = jnp.full((K,), dummy, jnp.int32).at[tgt].set(
                hid, mode="drop")
            sub = {f: h[f][idx] for f in names}
            shp = jax.tree.map(lambda a: a[idx], hp)
            B = rung_batch(cfg, K)
            if B > 1:
                sub = jax.lax.fori_loop(
                    0, B,
                    lambda _, s: _step_hot(s, proto, shp, sh, wend,
                                           cfg, names),
                    sub)
            else:
                sub = _step_hot(sub, proto, shp, sh, wend, cfg, names)
            return {f: h[f].at[idx].set(sub[f]) for f in names}
        return _scoped(f"k{K}", sparse)

    def make_chunked(K):
        # a one-event rung: every ready host steps once, K gathered rows
        # at a time (one chunk when nready <= K)
        def chunked(h):
            rank = jnp.cumsum(ready) - 1
            hid = jnp.arange(H, dtype=jnp.int32)

            def chunk(c, h2):
                lo = c * K
                take = ready & (rank >= lo) & (rank < lo + K)
                tgt = jnp.where(take, rank - lo, K).astype(jnp.int32)
                # empty slots hold H: they step a copy of the last row
                # and their scatter falls out of bounds, dropped
                idx = jnp.full((K,), H, jnp.int32).at[tgt].set(
                    hid, mode="drop")
                src = jnp.minimum(idx, H - 1)
                sub = {f: h2[f][src] for f in names}
                shp = jax.tree.map(lambda a: a[src], hp)
                sub = _step_hot(sub, proto, shp, sh, wend, cfg, names)
                return {f: h2[f].at[idx].set(sub[f], mode="drop")
                        for f in names}
            return jax.lax.fori_loop(0, (nready + K - 1) // K, chunk, h)
        return _scoped(f"k{K}", chunked)

    if not ks:
        return dense(hot), jnp.int32(0)

    # smallest rung that fits the ready count; len(ks) = dense
    rung = jnp.searchsorted(jnp.asarray(ks, jnp.int32), nready,
                            side="left").astype(jnp.int32)
    if cfg.active_block == -1 and ks[-1] == _ONE_EVENT_RUNG:
        # AUTO's one-event rung (1,024 <= H < 2,048) also runs the
        # dense passes, in chunks: the program then holds no third copy
        # of the handler machine (each is about 32 MB of device code at
        # 2,000 hosts), and the pass schedule is still the dense one
        branches = ([make_sparse(K) for K in ks[:-1]] +
                    [make_chunked(ks[-1])])
        return (jax.lax.switch(jnp.minimum(rung, len(ks) - 1), branches,
                               hot), rung)
    branches = [make_sparse(K) for K in ks] + [dense]
    return jax.lax.switch(rung, branches, hot), rung


# --- Window-boundary packet exchange --------------------------------------

def exsort_cap(cfg: EngineConfig) -> int:
    """Exchange sort-compaction cap (state.EngineConfig.exsortcap).
    Auto: the smallest power of two >= num_hosts (>= 2048) — big
    enough that a whole-population wave of one packet per host (the
    connect-wave worst case) still takes the compact path; multi-
    packet-per-host bursts beyond it fall back to the full sort."""
    N = cfg.num_hosts * cfg.obcap
    if cfg.exsortcap:
        return min(cfg.exsortcap, N)
    c = 2048
    while c < cfg.num_hosts and c < N:
        c *= 2
    return min(c, N)


def dst_cap(cfg: EngineConfig) -> int:
    """Destination-compaction cap for the arrival merge
    (state.EngineConfig.dstcap): when at most this many hosts received
    arrivals this window, only their rows are gathered/merged/
    scattered (merge_arrivals_at); more receivers fall back to the
    full-width merge. MUST be <= num_hosts: dummy slots duplicate a
    no-arrival destination, which is guaranteed to exist only while
    the receiving set is smaller than the host count."""
    if cfg.dstcap:
        return min(cfg.dstcap, cfg.num_hosts)
    return min(cfg.num_hosts, 4096)


def _intake_take(nfree, count_of, IN, cfg: EngineConfig):
    """THE per-destination intake policy — the single definition both
    exchange paths share (and the pyengine oracle mirrors,
    engine.pyengine._exchange): take = min(count, IN, headroom) where
    headroom = free queue slots less a reserve for protocol-internal
    pushes, floored at one arrival while at least TWO slots remain
    free (forward progress without starving internal pushes into
    ST_EQ_FULL_LOCAL — advisor round 3). With nfree <= 1 the arrival
    defers at the source; run_windows' anti-livelock advance drains
    the destination meanwhile."""
    reserve = min(8, cfg.qcap // 4)
    floor = jnp.where(nfree >= 2, 1, 0)
    allow = jnp.minimum(IN, jnp.maximum(nfree - reserve, floor))
    return jnp.minimum(count_of, allow)


def _trace_append(row, pkts, times, valid, dirv, on):
    """Append up to len(times) records to this host's trace ring
    (obs.pcap). Row-level under vmap; compiled only when tracing."""
    TC = row.tr_time.shape[0]
    take = valid & on
    k = jnp.sum(take).astype(jnp.int32)
    rank = jnp.cumsum(take) - 1
    pos = row.tr_cnt + rank.astype(jnp.int32)
    ok = take & (pos < TC)
    tgt = jnp.where(ok, pos, TC)
    return row.replace(
        tr_time=row.tr_time.at[tgt].set(times, mode="drop"),
        tr_pkt=row.tr_pkt.at[tgt].set(pkts, mode="drop"),
        tr_dir=row.tr_dir.at[tgt].set(jnp.int32(dirv), mode="drop"),
        tr_cnt=jnp.minimum(row.tr_cnt + k, TC),
        tr_drop=row.tr_drop + jnp.maximum(row.tr_cnt + k - TC, 0),
    )


def exchange(hosts, hp, sh, cfg: EngineConfig):
    """Route, loss-roll and deliver all outbox packets into destination
    event queues. Pure array program; runs once per window.

    Round-3 deferral semantics: a packet whose destination cannot take
    it this window (per-window intake budget or queue headroom spent)
    STAYS in the source outbox and re-exchanges next window with its
    send time — and therefore its arrival time — unchanged. Never a
    drop: the only modeled drop points are the topology reliability
    roll here and the NIC input buffer
    (shd-network-interface.c:288-311). Engine-capacity pressure shows
    up as ST_DEFER_FANIN, not as lost packets.

    Causal caveat (advisor round 3): the carry preserves arrival
    STAMPS, not execution order. By the window in which a deferred
    packet finally merges, its destination may already have executed
    events with later timestamps (e.g. an RTO that fired before the
    'earlier' ACK was processed), so the arrival's handler runs with a
    stale `now` against newer state. This matches the reference's
    behavior under resource pressure only loosely (the reference
    blocks the sender instead); both engines (this one and the
    pyengine oracle) implement the SAME rule, so differential tests
    stay exact, and TCP handlers are timestamp-robust (stale ACKs/
    segments are filtered by sequence state, not wall order)."""
    H, O, IN = cfg.num_hosts, cfg.obcap, cfg.incap
    N = H * O

    pkts = hosts.ob_pkt.reshape(N, P.PKT_WORDS)
    stimes = hosts.ob_time.reshape(N)
    valid = (jnp.arange(O)[None, :] < hosts.ob_cnt[:, None]).reshape(N)

    src = jnp.clip(pkts[:, P.SRC], 0, H - 1)
    dst = jnp.clip(pkts[:, P.DST], 0, H - 1)
    sv = sh.host_vertex[src]
    dv = sh.host_vertex[dst]
    lat = sh.lat_ns[sv, dv]
    rel = sh.rel[sv, dv]
    arrival = stimes + lat
    # handshake segments carry the one-way path latency (us) in SEQ:
    # the receiver's buffer autotuning reads RTT off the packet
    # instead of a per-row [V,V] table lookup (net.tcp._autotune)
    is_syn = (pkts[:, P.FLAGS] & P.F_SYN) != 0
    pkts = pkts.at[:, P.SEQ].set(
        jnp.where(is_syn, (lat // 1000).astype(jnp.int32),
                  pkts[:, P.SEQ]))

    # Deterministic per-packet drop roll keyed by the globally unique
    # (src, uid) stamped at NIC emit — the counter-based analogue of
    # worker_sendPacket's reliability test (shd-worker.c:238-244).
    # A carried packet re-rolls with the SAME (src, uid) key, so the
    # roll is stable across deferrals.
    u = R.cheap_uniform(R.stream_of(sh.seed32, R.DOMAIN_DROP, src),
                        pkts[:, P.UID])

    reachable = rel > 0
    deliver = valid & reachable & (u <= rel)
    net_dropped = valid & ~deliver

    # group-by-destination: stable sort once, then build the dense
    # [H, IN] inbound buffers entirely with GATHERS — the sorted order
    # makes every per-destination run contiguous, so cell (d, r) is
    # simply sorted position first_of[d] + r. (The previous
    # scatter-based construction dominated the whole window cost:
    # TPU scatters serialize.)
    #
    # Sort compaction (round 4): the argsort over all N = H x obcap
    # slots was itself the dominant window cost at scale (measured
    # ~110 ms/window at socks10k via tools/phase_profile.py — TPU
    # sorts are bitonic). Most windows ship a tiny fraction of N, so
    # when the survivor count fits cfg.exsortcap the valid entries are
    # first compacted (stable: compact rank is monotone in the
    # original index) and only the cap-sized list is sorted; a stable
    # sort of that subsequence equals the full stable sort filtered to
    # the survivors, so delivery order — and every downstream bit —
    # is unchanged. Oversized bursts fall back to the full sort.
    sortkey = jnp.where(deliver, dst, H)
    C = exsort_cap(cfg)
    if C < N and not cfg.tracecap:
        # At-scale path: sort compaction + destination-compacted merge
        # (both exact; see exsort_cap / merge_arrivals_at). The merge
        # runs INSIDE the branches so the dest-compacted variant can
        # touch [D] host rows instead of [H]. pcap tracing needs the
        # full [H, IN] inbound buffers, so traced runs take the static
        # path below instead.
        D = dst_cap(cfg)
        nval = jnp.sum(deliver, dtype=jnp.int32)
        merge_late = False

        def compact_tail(h):
            rank = jnp.cumsum(deliver) - 1
            tgt = jnp.where(deliver, rank, C).astype(jnp.int32)
            idx = jnp.full((C,), N, jnp.int32).at[tgt].set(
                jnp.arange(N, dtype=jnp.int32), mode="drop")
            live = idx < N
            idxc = jnp.minimum(idx, N - 1)
            key_c = jnp.where(live, sortkey[idxc], H)
            order_c = jnp.argsort(key_c, stable=True)
            sdst_c = key_c[order_c]
            # pre-gather ONCE into the sorted domain: the per-dest
            # window reads below then index a [C]-array, not [N]
            pkt_s = pkts[idxc][order_c]
            arr_s = arrival[idxc][order_c]
            dsts = jnp.arange(H, dtype=sdst_c.dtype)
            first_of = jnp.searchsorted(sdst_c, dsts, side="left")
            count_of = (jnp.searchsorted(sdst_c, dsts, side="right")
                        - first_of)
            has = count_of > 0
            ndst = jnp.sum(has, dtype=jnp.int32)

            def dst_compact(h):
                rankD = jnp.cumsum(has) - 1
                tgtD = jnp.where(has, rankD, D).astype(jnp.int32)
                # dummy rows: a destination with NO arrivals — its
                # merge is the identity (k = 0), so duplicates are
                # harmless (merge_arrivals_at docstring)
                dummy = jnp.argmin(has).astype(jnp.int32)
                idxD = jnp.full((D,), dummy, jnp.int32).at[tgtD].set(
                    jnp.arange(H, dtype=jnp.int32), mode="drop")
                nfreeD = jnp.sum(h.eq_time[idxD] == SIMTIME_MAX,
                                 axis=1, dtype=jnp.int32)
                take_ofD = _intake_take(nfreeD, count_of[idxD], IN, cfg)
                r = jnp.arange(IN)
                jD = jnp.clip(first_of[idxD][:, None] + r[None, :],
                              0, C - 1)
                cellD = r[None, :] < take_ofD[:, None]
                in_timeD = jnp.where(cellD, arr_s[jD], SIMTIME_MAX)
                in_pktD = jnp.where(cellD[:, :, None], pkt_s[jD],
                                    jnp.int32(0))
                take_full = jnp.zeros((H,), jnp.int32).at[idxD].set(
                    take_ofD, mode="drop")
                # accepted flags in the sorted domain
                dbc = jnp.clip(sdst_c, 0, H - 1)
                rank_s = jnp.arange(C) - first_of[dbc]
                kept_sorted = ((sdst_c < H) &
                               (rank_s < take_full[dbc]))
                h = merge_arrivals_at(h, cfg, in_pktD, in_timeD, idxD)
                return h, kept_sorted

            def dst_full(h):
                nfree = jnp.sum(h.eq_time == SIMTIME_MAX, axis=1,
                                dtype=jnp.int32)
                in_pkt, in_time, kept_sorted = _deliver_dense(
                    nfree, order_c, sdst_c, pkts[idxc], arrival[idxc],
                    IN, cfg)
                h = merge_arrivals(h, hp, cfg, in_pkt, in_time)
                return h, kept_sorted

            h, kept_sorted = jax.lax.cond(ndst <= D, dst_compact,
                                          dst_full, h)
            kept_c = jnp.zeros((C,), jnp.bool_).at[order_c].set(
                kept_sorted)
            kept = jnp.zeros((N,), jnp.bool_).at[idx].set(
                kept_c, mode="drop")
            return h, kept

        def full_tail(h):
            order = jnp.argsort(sortkey, stable=True)
            sdst = sortkey[order]
            nfree = jnp.sum(h.eq_time == SIMTIME_MAX, axis=1,
                            dtype=jnp.int32)
            in_pkt, in_time, kept_sorted = _deliver_dense(
                nfree, order, sdst, pkts, arrival, IN, cfg)
            h = merge_arrivals(h, hp, cfg, in_pkt, in_time)
            kept = jnp.zeros((N,), jnp.bool_).at[order].set(kept_sorted)
            return h, kept

        hosts, kept = jax.lax.cond(nval <= C, compact_tail, full_tail,
                                   hosts)
    else:
        # static path (small scale, or pcap tracing): full-width sort
        # and delivery; the merge runs LAST (below) so the trace ring
        # keeps its historical tx-before-rx record order — the rx
        # records are appended by merge_arrivals
        order = jnp.argsort(sortkey, stable=True)
        sdst = sortkey[order]
        nfree = jnp.sum(hosts.eq_time == SIMTIME_MAX, axis=1,
                        dtype=jnp.int32)
        in_pkt, in_time, kept_sorted = _deliver_dense(
            nfree, order, sdst, pkts, arrival, IN, cfg)
        kept = jnp.zeros((N,), jnp.bool_).at[order].set(kept_sorted)
        merge_late = True

    # tx trace records cover only packets that actually depart this
    # window (a carried packet is traced in the window it ships).
    # In the at-scale branches above the arrival merge has already
    # run; that is order-equivalent because tracing is off there
    # (tracecap == 0) and everything below touches disjoint state or
    # commuting stat columns.
    hosts = _trace_tx(hosts, hp, cfg, pkts, stimes,
                      (kept | net_dropped).reshape(H, O))
    stay = deliver & ~kept
    net_per_src = jnp.sum(net_dropped.reshape(H, O), axis=1,
                          dtype=jnp.int64)
    hosts = hosts.replace(stats=hosts.stats
                          .at[:, ST_PKTS_DROP_NET].add(net_per_src)
                          .at[:, ST_DEFER_FANIN].add(
        jnp.sum(stay.reshape(H, O), axis=1, dtype=jnp.int64)))
    hosts = _carry_outbox(hosts, pkts, stimes, arrival, stay, O)
    if merge_late:
        hosts = merge_arrivals(hosts, hp, cfg, in_pkt, in_time)
    return hosts


def _deliver_dense(nfree, order, sdst, pkts, arrival,
                   IN, cfg: EngineConfig, lo=0):
    """Shared gather-based delivery construction for both exchanges.
    `order`/`sdst` sort the (possibly gathered) global packet list by
    destination; builds this block's [Hl, IN] inbound buffers for hosts
    [lo, lo+Hl) (reshape-sums, no scatters). `nfree` is the caller's
    per-host free-queue-slot count [Hl].

    Takes and returns ONLY the small delivery arrays — not the Hosts
    pytree — so the compact-vs-full sort branches in the exchange
    carry ~the inbound buffers through lax.cond instead of the whole
    simulation state (conditional branch boundaries materialize their
    operands; at 10k hosts the state is ~0.5 GB per copy).

    Per-destination intake = min(IN, queue headroom): the IN window
    budget, bounded by the free event-queue slots less the reserve for
    protocol-internal pushes — floored at one arrival while at least
    TWO slots are free, so a jammed destination still makes progress
    without the floor consuming the last slot internal pushes need
    (no livelock either way: with nfree <= 1 the arrival defers at
    the source and run_windows' anti-livelock advance drains the
    destination). Returns kept_sorted, the accepted mask over the
    sorted list (False for entries destined outside this block), which
    the caller turns into source-side carries."""
    N = sdst.shape[0]
    Hl = nfree.shape[0]
    dsts = lo + jnp.arange(Hl, dtype=sdst.dtype)
    first_of = jnp.searchsorted(sdst, dsts, side="left")
    count_of = jnp.searchsorted(sdst, dsts, side="right") - first_of

    take_of = _intake_take(nfree, count_of, IN, cfg)

    r = jnp.arange(IN)
    j = jnp.clip(first_of[:, None] + r[None, :], 0, N - 1)  # [Hl, IN]
    oj = order[j]
    cell_ok = r[None, :] < take_of[:, None]
    in_time = jnp.where(cell_ok, arrival[oj], SIMTIME_MAX)
    in_pkt = jnp.where(cell_ok[:, :, None], pkts[oj], jnp.int32(0))

    # accepted flags in the sorted domain: rank within my dest block
    # < that dest's intake
    db = sdst - lo
    inblock = (db >= 0) & (db < Hl)
    dbc = jnp.clip(db, 0, Hl - 1)
    rank = jnp.arange(N) - first_of[dbc]
    kept_sorted = inblock & (rank < take_of[dbc])
    return in_pkt, in_time, kept_sorted


def _carry_outbox(hosts, pkts, stimes, arrival, stay, O):
    """Compact the packets in `stay` (original-order mask [Hl*O]) to
    the front of each source outbox; everything else departed. Records
    the earliest carried arrival in ob_next (window-advance bound).
    Callers count the carries into the appropriate defer stat."""
    Hl = hosts.stats.shape[0]
    stay2 = stay.reshape(Hl, O)
    ordr = jnp.argsort(~stay2, axis=1, stable=True)  # stayers first,
    #   original order preserved (stable sort of booleans)
    ob_pkt = jnp.take_along_axis(pkts.reshape(Hl, O, -1),
                                 ordr[:, :, None], axis=1)
    ob_time = jnp.take_along_axis(stimes.reshape(Hl, O), ordr, axis=1)
    cnt = jnp.sum(stay2, axis=1, dtype=jnp.int32)
    ob_next = jnp.min(jnp.where(stay2, arrival.reshape(Hl, O),
                                SIMTIME_MAX), axis=1)
    return hosts.replace(ob_pkt=ob_pkt, ob_time=ob_time, ob_cnt=cnt,
                         ob_next=ob_next)


def _trace_tx(hosts, hp, cfg: EngineConfig, pkts, stimes, departed):
    """Optional tx pcap records for the packets leaving the outbox
    this window (`departed` [Hl, O] mask; carried packets are traced
    in the window they finally ship). Loopback delivery bypasses the
    exchange and is not traced."""
    if not cfg.tracecap:
        return hosts
    Hl = hosts.stats.shape[0]
    O = departed.shape[1]
    return jax.vmap(_trace_append, in_axes=(0, 0, 0, 0, None, 0))(
        hosts, pkts.reshape(Hl, O, -1), stimes.reshape(Hl, O),
        departed, 1, hp.pcap_on)


def _merge_row(row, ipkt, itime, IN):
    """Merge one host's inbound arrivals into its queue free slots.
    Row-level under vmap; `row` may be a full Hosts row or the
    _MergeView slice of one (destination-compacted path)."""
    k = jnp.sum(itime != SIMTIME_MAX).astype(jnp.int32)
    free = row.eq_time == SIMTIME_MAX
    nfree = jnp.sum(free).astype(jnp.int32)
    k2 = jnp.minimum(k, nfree)
    frank = jnp.cumsum(free) - 1
    take = free & (frank < k2)
    j = jnp.clip(frank, 0, IN - 1)
    overflow = k - k2
    eq_time = jnp.where(take, itime[j], row.eq_time)
    return row.replace(
        eq_time=eq_time,
        eq_kind=jnp.where(take, EV_PKT, row.eq_kind),
        eq_seq=jnp.where(take, row.eq_ctr + frank.astype(jnp.int32),
                         row.eq_seq),
        eq_pkt=jnp.where(take[:, None], ipkt[j], row.eq_pkt),
        eq_ctr=row.eq_ctr + k2,
        eq_next=jnp.min(eq_time),  # cache invariant (state.eq_next)
        stats=radd(row.stats, ST_PKTS_DROP_Q, jnp.int64(overflow)),
    )


def merge_arrivals(hosts, hp, cfg: EngineConfig, in_pkt, in_time):
    """Shared tail of both exchanges (single-chip and sharded — ONE
    implementation so the bit-equality contract between them cannot
    drift): optional rx trace records, then the inbound merge into
    per-host queue free slots. The delivery construction already
    bounded each destination's intake by its queue headroom
    (_deliver_dense), so every arrival fits; the clamp here is a
    belt-and-braces guard — a nonzero ST_PKTS_DROP_Q is an engine
    bug, not a modeled drop."""
    IN = in_time.shape[1]

    if cfg.tracecap:
        hosts = jax.vmap(_trace_append, in_axes=(0, 0, 0, 0, None, 0))(
            hosts, in_pkt, in_time, in_time != SIMTIME_MAX, 0, hp.pcap_on)

    return jax.vmap(partial(_merge_row, IN=IN))(hosts, in_pkt, in_time)


@chex.dataclass
class _MergeView:
    """The subset of Hosts the arrival merge touches — gathered for
    just the destination rows in the compacted merge path, so the
    merge's per-row queue rewrites and data-dependent gathers scale
    with the number of RECEIVING hosts, not the host count (the
    xplane trace showed those gathers were ~45 ms of every socks10k
    window at [H, Q] width)."""
    eq_time: jnp.ndarray
    eq_kind: jnp.ndarray
    eq_seq: jnp.ndarray
    eq_pkt: jnp.ndarray
    eq_ctr: jnp.ndarray
    eq_next: jnp.ndarray
    stats: jnp.ndarray


_MERGE_FIELDS = ("eq_time", "eq_kind", "eq_seq", "eq_pkt", "eq_ctr",
                 "eq_next", "stats")


def merge_arrivals_at(hosts, cfg: EngineConfig, in_pkt, in_time, idxD):
    """Destination-compacted arrival merge: `in_pkt`/`in_time` are
    [D, IN] inbound buffers for the host rows named by idxD [D]
    (duplicates allowed ONLY for rows with zero arrivals — their merge
    is the identity, so duplicate scatters write identical bytes, the
    same argument as step_window_pass's dummy slots). Gathers only the
    merge-touched columns (_MergeView), merges, scatters back."""
    IN = in_time.shape[1]
    view = _MergeView(**{f: getattr(hosts, f)[idxD]
                         for f in _MERGE_FIELDS})
    merged = jax.vmap(partial(_merge_row, IN=IN))(view, in_pkt, in_time)
    return hosts.replace(**{
        f: getattr(hosts, f).at[idxD].set(getattr(merged, f))
        for f in _MERGE_FIELDS})


def update_cap_peaks(hosts):
    """Track peak occupancy of the fixed-capacity per-host arrays (one
    fused elementwise pass per window). Backs the end-of-run capacity
    report — the TPU analogue of the reference's ObjectCounter
    new/free accounting (shd-object-counter.c, reported at
    shd-slave.c:207-211): with no heap objects the failure mode is not
    a leak but an undersized array, so we report headroom instead.

    Sampled at window boundaries (after the drain for outbox/tx, after
    the merge for the queue), so short intra-window spikes can exceed
    the recorded peak — the overflow column of the report is the exact
    loss signal; peaks are a sizing hint."""
    eq_fill = jnp.sum(hosts.eq_time != SIMTIME_MAX, axis=1,
                      dtype=jnp.int32)
    sk_fill = jnp.sum(hosts.sk_used, axis=1, dtype=jnp.int32)
    cur = jnp.stack([eq_fill, sk_fill, hosts.ob_cnt, hosts.txq_cnt],
                    axis=1)
    return hosts.replace(cap_peaks=jnp.maximum(hosts.cap_peaks, cur))


# --- Multi-window driver ---------------------------------------------------

def next_event_time(hosts):
    """Global minimum pending EXECUTABLE event time (the barrier
    reduction). Drives the intra-window pass loop. Reads the cached
    per-host minima (state.eq_next), an [H] reduction, instead of
    scanning the full [H, Q] queue table every pass."""
    return jnp.min(hosts.eq_next)


def next_wakeup(hosts):
    """Window-advance bound: the earliest pending event OR the earliest
    arrival among source-carried packets (ob_next) — a deferred
    delivery must reopen the window even when no queue holds an event
    yet."""
    return jnp.minimum(jnp.min(hosts.eq_next), jnp.min(hosts.ob_next))


# One AOT-compiled instance per (cfg, max_windows), so the serving
# layer's disk tier can persist and reload each one (core.jitcache).
_RW_INSTANCES = {}


def run_windows_aot(cfg: EngineConfig, max_windows: int):
    """The AotJit wrapping the (cfg, max_windows) chunk program —
    shared by run_windows and the serving layer's pre-warm path
    (Simulation.prewarm compiles it without executing). The
    cache_scope carries the config fingerprint, so the persistent
    executable cache (serving.aotcache) keys this program stably
    across processes."""
    from ..core.jitcache import AotJit

    key = (cfg, max_windows)
    fn = _RW_INSTANCES.get(key)
    if fn is None:
        def impl(hosts, hp, sh, wstart, wend):
            return _run_windows_impl(hosts, hp, sh, wstart, wend, cfg,
                                     max_windows)

        impl.__name__ = f"run_windows_v{len(_RW_INSTANCES)}"
        impl.__qualname__ = impl.__name__
        from ..obs.ledger import fingerprint_of
        fn = AotJit(impl, donate_argnums=(0,),
                    cache_scope=(f"run_windows.c{max_windows}"
                                 f".{fingerprint_of(cfg)}"))
        _RW_INSTANCES[key] = fn
    return fn


_RWB_INSTANCES = {}


def run_windows_batch_aot(cfg: EngineConfig, max_windows: int,
                          batch: int):
    """The vmapped chunk program of the serving layer's scenario
    batching (serving.batch): `batch` same-shape scenarios stacked on
    a leading axis run the SAME (cfg, max_windows) program as
    run_windows, one compile for all of them. jax's while_loop
    batching rule freezes a finished lane's carry (select against the
    old value), so each lane's window trajectory is exactly its
    individual run's — byte-identical per digest chain
    (tests/test_serving.py)."""
    from ..core.jitcache import AotJit

    key = (cfg, max_windows, batch)
    fn = _RWB_INSTANCES.get(key)
    if fn is None:
        def impl(hosts, hp, sh, wstart, wend):
            return jax.vmap(
                lambda h, p, s, a, b: _run_windows_impl(
                    h, p, s, a, b, cfg, max_windows))(
                hosts, hp, sh, wstart, wend)

        impl.__name__ = f"run_windows_batch_v{len(_RWB_INSTANCES)}"
        impl.__qualname__ = impl.__name__
        from ..obs.ledger import fingerprint_of
        fn = AotJit(impl, donate_argnums=(0,),
                    cache_scope=(f"run_windows_batch.c{max_windows}"
                                 f".b{batch}.{fingerprint_of(cfg)}"))
        _RWB_INSTANCES[key] = fn
    return fn


def chunk_program_scopes() -> dict:
    """{module name: {HLO instruction name: op_name}} over the
    window-chunk programs this process materialized, single and
    batched (core.jitcache.AotJit.scope_map): what a device trace
    taken without HLO protos needs to put its ops under the pass
    labels (obs.passcope.attribute)."""
    out = {}
    for fn in [*_RW_INSTANCES.values(), *_RWB_INSTANCES.values()]:
        out.update(fn.scope_map())
    return out


def run_windows(hosts, hp, sh, wstart, wend, cfg: EngineConfig,
                max_windows: int):
    """Execute up to `max_windows` lookahead windows on device.

    Returns (hosts, wstart', wend', windows_run, pass_counts). The
    caller loops until wstart' >= stop_time or wstart' == SIMTIME_MAX
    (no events left). pass_counts is an i64 vector of lockstep passes
    executed per compaction rung — one entry per ladder_of(cfg) rung
    plus the trailing dense fallback — feeding the SimReport cost
    model (the TPU analogue of the reference's self-reported scheduler
    idle/barrier seconds, shd-scheduler.c:250-252).
    """
    return run_windows_aot(cfg, max_windows)(hosts, hp, sh, wstart,
                                             wend)


def _run_windows_impl(hosts, hp, sh, wstart, wend, cfg: EngineConfig,
                      max_windows: int):
    NR = len(pass_labels(cfg))  # pass-mix counters (SimReport cost)

    def win_cond(carry):
        _, ws, _, i, _ = carry
        return (i < max_windows) & (ws < sh.stop_time) & (ws < SIMTIME_MAX)

    def win_body(carry):
        hosts, ws, we, i, pc = carry
        # never execute past the simulation end (the reference clamps the
        # execution window to endTime, shd-master.c:410-440)
        we_eff = jnp.minimum(we, sh.stop_time)
        ran = next_event_time(hosts) < we_eff  # >=1 event will execute

        # named_scope stamps carry the stateflow entry names into the
        # compiled HLO metadata so the passcope observatory
        # (obs/passcope.py) attributes decoded device self-times back
        # to these passes — naming only, never math
        with jax.named_scope("drain"):
            hosts, pc = drain_window(hosts, hp, sh, we_eff, cfg, pc)
        with jax.named_scope("cap_peaks"):
            hosts = update_cap_peaks(hosts)
        ob0 = jnp.sum(hosts.ob_cnt)
        # an empty exchange is the identity: skip its sort/gather work
        # for windows that emitted nothing (common in sparse phases).
        # Single-chip only — the sharded body's collectives must run
        # uniformly on every shard.
        with jax.named_scope("exchange"):
            hosts = jax.lax.cond(
                jnp.any(hosts.ob_cnt > 0),
                lambda h: exchange(h, hp, sh, cfg),
                lambda h: h, hosts)
        # second sample catches the queue right after arrivals merged
        with jax.named_scope("cap_peaks"):
            hosts = update_cap_peaks(hosts)
        # Anti-livelock: a window that executed nothing AND shipped
        # nothing (every carried packet's destination still jammed)
        # must not re-open at the same carried arrival forever —
        # advance to the earliest queue event instead so the jammed
        # destination drains (its events execute, freeing intake).
        with jax.named_scope("advance"):
            progressed = ran | (jnp.sum(hosts.ob_cnt) < ob0)
            nt = jnp.where(progressed, next_wakeup(hosts),
                           next_event_time(hosts))
            we2 = jnp.where(nt == SIMTIME_MAX, SIMTIME_MAX,
                            nt + sh.min_jump)
        return hosts, nt, we2, i + 1, pc

    return jax.lax.while_loop(
        win_cond, win_body,
        (hosts, wstart, wend, jnp.int32(0), jnp.zeros((NR,), jnp.int64)))


# --- Determinism-digest canonicalization (obs.digest) ---------------------
# Host-side, numpy-only: this module owns the slot conventions (free
# event-queue slots, outbox compaction tails, NIC/trace/wake ring
# bounds), so the rules zeroing DEAD slots before hashing live here —
# next to the device code whose conventions they restate.

def canonicalize_state(arrs: dict) -> dict:
    """Zero dead slots in a host-side copy of the Hosts arrays so
    semantically identical states hash identically.

    Dead slots legitimately retain stale bytes that may differ between
    equal runs (the sharded exchange compacts outboxes differently
    than the single-chip one; q_clear_slot frees a slot without
    scrubbing its payload; closed socket rows keep their last values).
    The digest chain is a statement about LIVE state only:

    - event queue: slots with eq_time == SIMTIME_MAX are free — their
      seq/kind/payload words are scrubbed (equeue.q_clear_slot only
      resets time and kind);
    - outbox: slots at index >= ob_cnt are exchange-compaction tail;
    - NIC tx ring: positions outside [txq_head, txq_head + txq_cnt);
    - hosted-wake / packet-trace rings: slots >= hw_cnt / tr_cnt
      (append-with-drop, never wrapped — _trace_append, bridge.py);
    - socket table: rows with sk_used False are scrubbed wholesale.

    `arrs` maps Hosts field name -> numpy array (leading dim H); a new
    dict of (copied where modified) arrays is returned. Device state
    is never touched.
    """
    import numpy as np

    a = dict(arrs)

    # Narrow at-rest layout (state.NARROW_SPEC, cfg.wide_state == 0):
    # decode every narrowed column back to its canonical wide dtype —
    # and the delta-encoded scoreboards back to absolute stream
    # offsets — BEFORE any hashing or scrubbing. The digest hashes
    # dtype+shape headers per column, so without this a narrowed run
    # could never chain byte-identically to a --wide-state one; and
    # the socket scrub below must see the scoreboards' dead-slot
    # sentinel in ONE encoding (a freed slot's stale relative values
    # decode to garbage absolutes, which the sk_used scrub then zeroes
    # exactly like the wide run's stale absolutes). Order matters: the
    # abs columns first, so the rel anchors (sk_rcv_nxt/sk_snd_una)
    # are wide when the scoreboards decode against them.
    for f, (wdt, _ndt) in NARROW_ABS.items():
        if f in a and a[f].dtype != np.dtype(wdt):
            a[f] = a[f].astype(wdt)
    for f, (wdt, _ndt, anchor) in NARROW_REL.items():
        if f in a and a[f].dtype != np.dtype(wdt):
            rel = a[f]
            anc = a[anchor]
            a[f] = np.where(rel >= 0, rel.astype(wdt) + anc[..., None],
                            np.array(-1, wdt))

    def scrub(key, dead):
        v = a[key]
        m = dead
        while m.ndim < v.ndim:
            m = m[..., None]
        a[key] = np.where(m, np.zeros((), v.dtype), v)

    free = a["eq_time"] == SIMTIME_MAX
    for k in ("eq_seq", "eq_kind", "eq_pkt"):
        scrub(k, free)

    O = a["ob_time"].shape[1]
    dead_ob = np.arange(O)[None, :] >= a["ob_cnt"][:, None]
    for k in ("ob_time", "ob_pkt"):
        scrub(k, dead_ob)

    T = a["txq_pkt"].shape[1]
    pos = (np.arange(T)[None, :] - a["txq_head"][:, None]) % T
    scrub("txq_pkt", pos >= a["txq_cnt"][:, None])

    HW = a["hw_time"].shape[1]
    dead_hw = np.arange(HW)[None, :] >= a["hw_cnt"][:, None]
    for k in ("hw_time", "hw_pkt"):
        scrub(k, dead_hw)

    TC = a["tr_time"].shape[1]
    dead_tr = np.arange(TC)[None, :] >= a["tr_cnt"][:, None]
    for k in ("tr_time", "tr_pkt", "tr_dir"):
        scrub(k, dead_tr)

    unused = ~a["sk_used"]
    for k in arrs:
        if k.startswith("sk_") and k != "sk_used":
            scrub(k, unused)
    return a
