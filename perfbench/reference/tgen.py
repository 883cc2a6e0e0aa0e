"""Copied from the system under test (apps/tgen.py) so that the plain
reference imports nothing of the program.

The node-table encoding and the behaviour-graph compiler only."""

from __future__ import annotations

import os
import re
from xml.etree import ElementTree

import numpy as np

from .simtime import SIMTIME_ONE_SECOND

# --- node table encoding (Shared.tgen_nodes: int64 [N, 10]) ---
# [kind, a, b, c, next, peers_off, n_peers, sync_ref, edge_off, edge_cnt]
# `next` = first successor, kept as a debugging/inspection convenience
# (tests walk it); the device walk routes ONLY through the edge pool
# (edge_off/edge_cnt -> Shared.tgen_edges).
NK_START = 0      # a=serverport, b=initial delay ns
NK_TRANSFER = 1   # a=type (0 get, 1 put), b=size bytes,
#                   c=timeout ns, sync_ref=stallout ns
NK_PAUSE = 2      # a=fixed time ns (or -1: draw from pool[b:b+c])
NK_END = 3        # a=count limit, b=time-limit ns, c=size-limit bytes
NK_SYNC = 4       # a=indegree (arrivals required), sync_ref=counter slot
(COL_KIND, COL_A, COL_B, COL_C, COL_NEXT, COL_POFF, COL_PCNT, COL_REF,
 COL_EOFF, COL_ECNT) = range(10)
NODE_COLS = 10

# walk-cursor work stack depth (per wake); forks beyond this are
# dropped and counted in ST_TGEN_DROP
STACK_CAP = 8

# app_r register use: r2=transfers completed, r3=bytes transferred,
# r4=walk start time, r5=done flag (end conditions met)
REG_COUNT = 2
REG_BYTES = 3
REG_T0 = 4
REG_DONE = 5

# transfer request tag riding the SYN (31 usable bits)
TAG_PUT = 1 << 30
TAG_SIZE_MASK = (1 << 30) - 1

# transfer abort limits (shd-tgen-transfer.c:9-11); 0/unset in the
# graph falls back to these, exactly like the reference
DEFAULT_XFER_TIMEOUT_NS = 60 * SIMTIME_ONE_SECOND
DEFAULT_XFER_STALLOUT_NS = 15 * SIMTIME_ONE_SECOND

# watchdog timer wake: AUX sentinel (distinct from the walk
# continuations, which use aux >= 0 / small negative retry encodings)
WD_AUX = -(1 << 20)

_SIZE_RE = re.compile(r"^\s*([0-9.]+)\s*([a-zA-Z]*)\s*$")
_SIZE_UNITS = {
    "": 1, "b": 1, "byte": 1, "bytes": 1,
    "kb": 10**3, "mb": 10**6, "gb": 10**9, "tb": 10**12,
    "kib": 2**10, "mib": 2**20, "gib": 2**30, "tib": 2**40,
}


def parse_size(text: str) -> int:
    """Parse tgen size strings: '100 KiB', '1 MiB', '5242880'."""
    m = _SIZE_RE.match(str(text))
    if not m:
        raise ValueError(f"bad size {text!r}")
    val, unit = m.groups()
    mult = _SIZE_UNITS.get(unit.lower())
    if mult is None:
        raise ValueError(f"bad size unit {unit!r} in {text!r}")
    return int(float(val) * mult)


def _parse_tgen_seconds(text: str) -> int:
    """tgen times are seconds (may be fractional)."""
    return int(float(text) * SIMTIME_ONE_SECOND)


class TgenTables:
    """Accumulates compiled behavior graphs into the shared device
    tables (deduplicated per distinct graph)."""

    def __init__(self):
        self.nodes = []    # rows of NODE_COLS int64
        self.peers = []    # (host, port) int32 rows
        self.pool = []     # int64 pause choices (ns)
        self.edges = []    # int32 absolute successor-node indices
        self.sync_slots = 0  # per-host synchronize counters allocated
        self._cache = {}

    def compile(self, source: str, dns) -> int:
        """Compile a behavior graphml (path or inline text); returns the
        start-node index into the node table."""
        key = source
        if key in self._cache:
            return self._cache[key]
        start = compile_tgen_graph(source, dns, self)
        self._cache[key] = start
        return start

    def arrays(self):
        nodes = (np.asarray(self.nodes, dtype=np.int64)
                 if self.nodes else np.zeros((1, NODE_COLS), np.int64))
        peers = (np.asarray(self.peers, dtype=np.int32)
                 if self.peers else np.zeros((1, 2), np.int32))
        pool = (np.asarray(self.pool, dtype=np.int64)
                if self.pool else np.zeros((1,), np.int64))
        edges = (np.asarray(self.edges, dtype=np.int32)
                 if self.edges else np.full((1,), -1, np.int32))
        return nodes, peers, pool, edges


def _resolve_peers(text: str, dns):
    """'server1:30080,server2:30080' -> [(host_id, port), ...]"""
    out = []
    for item in str(text).split(","):
        item = item.strip()
        if not item:
            continue
        name, _, port = item.partition(":")
        out.append((dns.resolve(name), int(port or 80)))
    return out


def compile_tgen_graph(source: str, dns, tab: TgenTables) -> int:
    """Flatten one tgen behavior graphml into `tab`; returns start index."""
    if os.path.exists(source):
        with open(source) as f:
            text = f.read()
    else:
        text = source
    root = ElementTree.fromstring(text)
    ns = ""
    if root.tag.startswith("{"):
        ns = root.tag[: root.tag.index("}") + 1]

    keys = {}  # key id -> attr name
    for k in root.iter(f"{ns}key"):
        keys[k.attrib["id"]] = k.attrib["attr.name"]

    graph = root.find(f"{ns}graph")
    if graph is None:
        raise ValueError("tgen graphml has no <graph>")

    raw = {}      # node id -> attr dict
    order = []    # node ids in file order
    for nd in graph.findall(f"{ns}node"):
        attrs = {}
        for d in nd.findall(f"{ns}data"):
            attrs[keys.get(d.attrib["key"], d.attrib["key"])] = (d.text or "")
        raw[nd.attrib["id"]] = attrs
        order.append(nd.attrib["id"])

    succs = {nid: [] for nid in order}   # node id -> successor ids (file order)
    indeg = {nid: 0 for nid in order}    # node id -> incoming edge count
    for e in graph.findall(f"{ns}edge"):
        s, t = e.attrib["source"], e.attrib["target"]
        if s in succs and t in indeg:
            succs[s].append(t)
            indeg[t] += 1

    base = len(tab.nodes)
    index = {nid: base + i for i, nid in enumerate(order)}

    def action_of(nid: str) -> str:
        for prefix in ("start", "transfer", "pause", "synchronize", "end"):
            if nid.startswith(prefix):
                return prefix
        raise ValueError(f"tgen node id {nid!r} names no known action")

    default_peers = None
    # graph-wide abort limits from the start node (pre-scanned: file
    # order does not guarantee start first); the reference's fallback
    # chain is transfer attr -> start attr -> built-in default
    # (shd-tgen-action.c:476-487,810, shd-tgen-transfer.c:972-973)
    default_timeout = DEFAULT_XFER_TIMEOUT_NS
    default_stallout = DEFAULT_XFER_STALLOUT_NS
    for nid in order:
        if action_of(nid) == "start":
            a = raw[nid]
            if a.get("timeout"):
                default_timeout = (_parse_tgen_seconds(a["timeout"])
                                   or DEFAULT_XFER_TIMEOUT_NS)
            if a.get("stallout"):
                default_stallout = (_parse_tgen_seconds(a["stallout"])
                                    or DEFAULT_XFER_STALLOUT_NS)
    rows = []
    for nid in order:
        a = raw[nid]
        act = action_of(nid)
        slist = [index[t] for t in succs[nid]]
        nxt = slist[0] if slist else -1
        eoff, ecnt = len(tab.edges), len(slist)
        tab.edges.extend(slist)
        poff = pcnt = 0
        if act == "start":
            peers = _resolve_peers(a.get("peers", ""), dns)
            if peers:
                poff = len(tab.peers)
                pcnt = len(peers)
                tab.peers.extend(peers)
                default_peers = (poff, pcnt)
            port = int(a.get("serverport", 0) or 0)
            delay = _parse_tgen_seconds(a["time"]) if a.get("time") else 0
            row = [NK_START, port, delay, 0, nxt, poff, pcnt, 0, eoff, ecnt]
        elif act == "transfer":
            ttype = 1 if a.get("type", "get").lower() == "put" else 0
            size = parse_size(a.get("size", "1 MiB"))
            if a.get("peers"):
                peers = _resolve_peers(a["peers"], dns)
                poff, pcnt = len(tab.peers), len(peers)
                tab.peers.extend(peers)
            elif default_peers:
                poff, pcnt = default_peers
            else:
                # the reference tgen errors the same way: a transfer
                # with neither its own peers nor start-node peers
                raise ValueError(
                    f"tgen transfer node {nid!r} has no peers (set a "
                    "'peers' attr on it or on the start node)")
            tmo = (_parse_tgen_seconds(a["timeout"]) if a.get("timeout")
                   else 0) or default_timeout
            stl = (_parse_tgen_seconds(a["stallout"]) if a.get("stallout")
                   else 0) or default_stallout
            row = [NK_TRANSFER, ttype, size, tmo, nxt, poff, pcnt, stl,
                   eoff, ecnt]
        elif act == "pause":
            t = a.get("time", "1")
            if "," in t:
                choices = [_parse_tgen_seconds(x)
                           for x in t.split(",") if x.strip()]
                ref = len(tab.pool)
                tab.pool.extend(choices)
                row = [NK_PAUSE, -1, ref, len(choices), nxt, 0, 0, 0, eoff,
                       ecnt]
            else:
                row = [NK_PAUSE, _parse_tgen_seconds(t), 0, 0, nxt, 0, 0, 0,
                       eoff, ecnt]
        elif act == "synchronize":
            # join barrier: fires after `indegree` cursor arrivals
            sref = tab.sync_slots
            tab.sync_slots += 1
            row = [NK_SYNC, max(indeg[nid], 1), 0, 0, nxt, 0, 0, sref, eoff,
                   ecnt]
        else:  # end
            count = int(a.get("count", 0) or 0)
            tlim = _parse_tgen_seconds(a["time"]) if a.get("time") else 0
            slim = parse_size(a["size"]) if a.get("size") else 0
            row = [NK_END, count, tlim, slim, nxt, 0, 0, 0, eoff, ecnt]
        rows.append(row)
    tab.nodes.extend(rows)

    if "start" not in index:
        raise ValueError("tgen graph has no 'start' node")

    # Reject walks that can spin forever on device: the subgraph of
    # transitions that complete instantly (no timer, no socket) must be
    # acyclic, or the walk loop would chain through a cycle unboundedly
    # within one wake. Blocking nodes: transfers, pauses with a
    # guaranteed-nonzero wait, delayed starts, and multi-arrival
    # synchronize barriers.
    def blocks(local_i: int) -> bool:
        r = rows[local_i]
        if r[COL_KIND] == NK_TRANSFER:
            return True
        if r[COL_KIND] == NK_PAUSE:
            if r[COL_A] > 0:
                return True
            if r[COL_A] < 0:  # drawn from pool: blocking iff no 0 choice
                lo, n = r[COL_B], r[COL_C]
                return min(tab.pool[lo:lo + n]) > 0
            return False
        if r[COL_KIND] == NK_START:
            return r[COL_B] > 0
        if r[COL_KIND] == NK_SYNC:
            return r[COL_A] > 1
        return False

    WHITE, GRAY, BLACK = 0, 1, 2

    # iterative DFS over the non-blocking subgraph
    def succ_local(i):
        r = rows[i]
        return [tab.edges[r[COL_EOFF] + j] - base for j in range(r[COL_ECNT])]

    state = [WHITE] * len(rows)
    for root_i in range(len(rows)):
        if state[root_i] != WHITE or blocks(root_i):
            continue
        stack = [(root_i, 0)]
        state[root_i] = GRAY
        while stack:
            i, j = stack[-1]
            ss = [s for s in succ_local(i) if not blocks(s)]
            if j < len(ss):
                stack[-1] = (i, j + 1)
                s = ss[j]
                if state[s] == GRAY:
                    names = [order[x] for x, _ in stack] + [order[s]]
                    raise ValueError(
                        "tgen graph cycle never blocks (no transfer or "
                        f"nonzero pause): {' -> '.join(names)}")
                if state[s] == WHITE:
                    state[s] = GRAY
                    stack.append((s, 0))
            else:
                state[i] = BLACK
                stack.pop()

    return index["start"]
