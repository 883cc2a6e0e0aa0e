"""Copied from the system under test (net/congestion.py) so that the plain
reference imports nothing of the program.

The same functions on numpy scalars, in the same float32 precision;
the cubic term is the multiply chain jax lowers ``x ** 3`` to."""

from __future__ import annotations

import numpy as jnp

CC_AIMD = 0
CC_RENO = 1
CC_CUBIC = 2

# Reference cubic constants (shd-tcp-cubic.c — NOT the Linux-kernel
# values): cubic_new sets beta=819 against BETA_SCALE=1024 (Linux uses
# 717), so the loss decrease is W*819/1024 ~ 0.8W and fast convergence
# is W*(1024+819)/2048 ~ 0.9W (cubic_packetLoss, shd-tcp-cubic.c:
# 224-236). The growth constant: _cubic_update computes
# originDelta = (rttScale * offset_ms^3) >> 40 with rttScale =
# scalingFactor*10 = 410 and time in MILLISECONDS (shd-tcp-cubic.c:
# 112-160), i.e. C = 410e9/2^40 ~ 0.3729 pkt/s^3 (the ms time base
# makes this differ from Linux's 0.4, which scales jiffies<<10).
_CUBIC_BETA = 819.0 / 1024.0
_CUBIC_C = 410.0 * 1e9 / float(1 << 40)

_NS = 1e-9  # ns -> seconds


def on_ack(kind, cwnd, ssthresh, wmax, epoch, k, npkts, now, srtt_ns):
    """Congestion avoidance on new-data ACK.

    Args are per-socket scalars (or broadcastable arrays); `kind` is the
    runtime cc selector, `npkts` the number of full segments this ACK
    newly covered, `now` sim time ns, `srtt_ns` the socket's delayMin
    (minimum RTT sample; callers fall back to srtt before the first
    min) — <=0 falls back to the reference's 100ms default
    (shd-tcp-cubic.c:72-74).
    Returns (cwnd', epoch', k').
    """
    npkts_f = npkts.astype(jnp.float32)
    in_ss = (ssthresh == 0.0) | (cwnd < ssthresh)
    if jnp.ndim(in_ss) == 0 and in_ss:
        # slow start selects the grown window and keeps epoch and K:
        # the cubic terms below would be computed and discarded
        return cwnd + npkts_f, epoch, k

    # --- slow start (all kinds): window += packets acked ---
    ss_cwnd = cwnd + npkts_f

    # --- aimd/reno additive increase: ceil/frac of n^2/window ---
    add_cwnd = cwnd + (npkts_f * npkts_f) / jnp.maximum(cwnd, 1.0)

    # --- cubic: W(t) = C*(t-K)^3 + wmax, one epoch per loss-free run ---
    fresh = epoch < 0
    epoch2 = jnp.where(fresh, now, epoch)
    k_calc = jnp.cbrt(jnp.maximum(wmax - cwnd, 0.0) / _CUBIC_C)
    k2 = jnp.where(fresh, k_calc, k)
    t = (now - epoch2).astype(jnp.float32) * _NS
    # the curve's origin is FIXED for the epoch (the reference's
    # originPoint, shd-tcp-cubic.c:137-144): wmax when a loss has been
    # seen (post-loss wmax >= cwnd always holds: decrease is 0.8x,
    # fast convergence keeps >= 0.9x), else the pre-loss probe grows
    # from the current window. A moving origin (max(wmax, cwnd)) made
    # the target self-referential past the plateau — growth then
    # saturated at the rate cap instead of following the cubic.
    origin = jnp.where(wmax > 0.0, wmax, cwnd)
    target = _CUBIC_C * (t - k2) ** 3 + origin
    # Growth-rate cap, the reference's minCount floor
    # (shd-tcp-cubic.c:168-173): count never drops below
    # W*1000*8/(10*16*delayMin), and count halves, so the per-ack
    # increment is bounded by delayMin_ms/(25*W) — i.e. at most
    # 0.04*RTT_ms packets per RTT once past the plateau. Without this
    # the target's cubic ramp lets the chase step saturate at one
    # packet per ack = doubling every RTT, unbounded (caught by the
    # golden trajectory test).
    srtt_ms = jnp.where(srtt_ns > 0,
                        srtt_ns.astype(jnp.float32) * 1e-6,
                        jnp.float32(100.0))
    rate_cap = npkts_f * srtt_ms / (25.0 * jnp.maximum(cwnd, 1.0))
    cubic_step = jnp.where(target > cwnd,
                           jnp.minimum((target - cwnd) /
                                       jnp.maximum(cwnd, 1.0), rate_cap),
                           0.01 / jnp.maximum(cwnd, 1.0))
    cubic_cwnd = cwnd + jnp.minimum(cubic_step, npkts_f)

    avoid_cwnd = jnp.where(kind == CC_CUBIC, cubic_cwnd, add_cwnd)
    cwnd2 = jnp.where(in_ss, ss_cwnd, avoid_cwnd)
    # epoch/k only meaningful for cubic avoidance; harmless otherwise
    epoch2 = jnp.where(in_ss, epoch, epoch2)
    k2 = jnp.where(in_ss, k, k2)
    return cwnd2, epoch2, k2


def on_loss(kind, cwnd, ssthresh, wmax):
    """Multiplicative decrease on a loss event (fast retransmit or RTO).

    Mirrors the reference's packetLoss vtable calls and the caller's
    `threshold = packetLoss(); window = threshold` contract
    (shd-tcp.c:1063-1064).
    Returns (cwnd', ssthresh', wmax', epoch'=-1).
    """
    # aimd/reno: halve (shd-tcp-aimd.c:44-60)
    half = jnp.maximum(jnp.ceil(cwnd / 2.0), 1.0)
    # cubic: fast convergence on wmax, beta decrease (shd-tcp-cubic.c:224-236)
    wmax2 = jnp.where(cwnd < wmax, cwnd * (1.0 + _CUBIC_BETA) / 2.0, cwnd)
    cub = jnp.maximum(cwnd * _CUBIC_BETA, 2.0)

    new_wnd = jnp.where(kind == CC_CUBIC, cub, half)
    return (new_wnd, new_wnd,
            jnp.where(kind == CC_CUBIC, wmax2, wmax),
            jnp.int64(-1))
