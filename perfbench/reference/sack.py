"""The SACK scoreboard of the system under test (net/sack.py), written
for the plain reference: the same operations, on Python lists of K
ints, lane for lane (the device's masked lane arithmetic becomes a
loop over the K lanes; its stable argsort, a stable sort)."""

from __future__ import annotations

from .constants import TCP_MSS

# scoreboard capacity: concurrent tracked holes beyond this degrade to
# go-back-N via the RTO, never to wrong data
K = 4

_INF = 2**62


def empty():
    """-> (starts, ends) with no ranges."""
    return [-1] * K, [-1] * K


def _sorted_pack(s, e):
    """Sort ranges ascending by start with empty slots (-1) last."""
    order = sorted(range(len(s)), key=lambda i: _INF if s[i] < 0 else s[i])
    return [s[i] for i in order], [e[i] for i in order]


def insert(s, e, ns, ne):
    """Add range [ns, ne) to the set, merging any overlapping or
    touching ranges; on overflow the HIGHEST range is discarded."""
    return insert_counted(s, e, ns, ne)[:2]


def insert_counted(s, e, ns, ne):
    """:func:`insert` that also reports the overflow: (s, e, dropped)
    with dropped = 1 when a valid range was discarded."""
    new_ok = ne > ns
    if not new_ok and s[0] < 0:      # empty set (empties sort last)
        return list(s), list(e), 0
    ms, me = ns, ne
    cs, ce = [], []
    for si, ei in zip(s, e):
        valid = si >= 0
        ov = valid and new_ok and ns <= ei and ne >= si
        if ov:
            ms = min(ms, si)
            me = max(me, ei)
        keep = valid and not ov
        cs.append(si if keep else -1)
        ce.append(ei if keep else -1)
    cs.append(ms if new_ok else -1)
    ce.append(me if new_ok else -1)
    cs, ce = _sorted_pack(cs, ce)
    return cs[:K], ce[:K], 1 if cs[K] >= 0 else 0


def consume(s, e, rcv):
    """Advance the in-order cursor `rcv` through any ranges it reaches,
    absorbing them. Returns (s, e, rcv')."""
    if s[0] < 0:                     # empty set
        return list(s), list(e), rcv
    s, e = list(s), list(e)
    for _ in range(K):
        hit = [si >= 0 and si <= rcv for si in s]
        rcv = max(rcv, max((ei if h else -1) for ei, h in zip(e, hit)))
        s = [-1 if h else si for si, h in zip(s, hit)]
        e = [-1 if h else ei for ei, h in zip(e, hit)]
    return (*_sorted_pack(s, e), rcv)


def drop_below(s, e, lo):
    """Remove ranges fully below `lo` and clip partial overlap."""
    if s[0] < 0:
        return list(s), list(e)
    s2, e2 = [], []
    for si, ei in zip(s, e):
        valid = si >= 0
        gone = valid and ei <= lo
        s2.append(-1 if gone else (max(si, lo) if valid else si))
        e2.append(-1 if gone else ei)
    return _sorted_pack(s2, e2)


def skip(x, s, e):
    """First offset >= x not inside any range."""
    return max(x, max((ei if (si >= 0 and si <= x < ei) else -1)
                      for si, ei in zip(s, e)))


def next_start_after(x, s, e):
    """Smallest range start > x; _INF if none."""
    return min((si if (si >= 0 and si > x) else _INF) for si in s)


def any_range(s):
    return any(si >= 0 for si in s)


def max_end(s, e):
    """Highest sacked offset (-1 when the set is empty)."""
    return max((ei if si >= 0 else -1) for si, ei in zip(s, e))


def lost_bound(s, e, una, hole_end):
    """Upper bound of inferably-lost bytes for fast recovery."""
    me = max_end(s, e)
    return min(hole_end, me if me > 0 else una + TCP_MSS)


# --- wire encoding: 15-bit (offset, length) in MSS units ---------------------

def _encode_one(s_i, e_i, ack):
    has = s_i >= 0
    rel_raw = (s_i - ack + TCP_MSS - 1) // TCP_MSS
    rel = min(max(rel_raw, 0), 0x7FFF)
    a_s = ack + rel * TCP_MSS
    ln = min(max((e_i - a_s) // TCP_MSS, 0), 0x7FFF)
    ok = has and ln > 0 and rel_raw <= 0x7FFF
    return (rel << 1) | (ln << 16) if ok else 0


def encode2(s, e, ack):
    """The two lowest ranges as packed words for the AUX and APP
    header fields; 0 = no block."""
    return _encode_one(s[0], e[0], ack), _encode_one(s[1], e[1], ack)


def decode(word, ack, hi):
    """Packed word -> (start, end) clipped to [ack, hi); (-1, -1) when
    absent."""
    rel = (word >> 1) & 0x7FFF
    ln = (word >> 16) & 0x7FFF
    s = ack + rel * TCP_MSS
    e = min(s + ln * TCP_MSS, hi)
    ok = ln > 0 and e > s
    return (s if ok else -1), (e if ok else -1)
