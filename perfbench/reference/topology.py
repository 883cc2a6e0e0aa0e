"""Copied from the system under test (routing/topology.py) so that the plain
reference imports nothing of the program.

The all-pairs routes take the scipy path only."""

from __future__ import annotations

import ipaddress
from dataclasses import dataclass

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from .simtime import SIMTIME_ONE_MILLISECOND
from .graphml import Graph, parse_graphml


@dataclass
class Topology:
    graph: Graph
    latency_ns: np.ndarray       # [V, V] int64 path latency
    reliability: np.ndarray      # [V, V] float32 path delivery probability
    min_latency_ns: int          # min over all pairs (window lookahead bound)
    v_bw_up_bytes: np.ndarray    # [V] vertex default bandwidths, bytes/s
    v_bw_down_bytes: np.ndarray

    @property
    def num_vertices(self) -> int:
        return self.graph.num_vertices

    def has_edge(self, v1: int, v2: int) -> bool:
        """True when a direct edge joins the two vertices (either
        direction on undirected graphs). Fault injection uses this to
        warn when a link_down/loss/latency fault names a pair that is
        only connected through intermediate hops — the fault still
        applies, but to the precomputed PATH entry [v1, v2], not to
        every path crossing a physical link (the oracle stores paths,
        not edges; see engine.faults)."""
        g = self.graph
        m = (g.e_src == v1) & (g.e_dst == v2)
        if not g.directed:
            m |= (g.e_src == v2) & (g.e_dst == v1)
        return bool(m.any())


def _build_adjacency(g: Graph):
    """Dense-ish CSR of min edge latency between distinct vertices, plus
    per-pair packetloss of the chosen (minimum-latency) edge."""
    V = g.num_vertices
    src, dst = g.e_src, g.e_dst
    lat, loss = g.e_latency_ms, g.e_packetloss
    if not g.directed:
        keep = src != dst
        src = np.concatenate([src, dst[keep]])
        dst = np.concatenate([dst, g.e_src[keep]])
        lat = np.concatenate([lat, lat[keep]])
        loss = np.concatenate([loss, loss[keep]])
    # Keep the minimum-latency edge per (src, dst) pair (parallel edges).
    order = np.lexsort((lat, dst, src))
    src, dst, lat, loss = src[order], dst[order], lat[order], loss[order]
    pair = src * V + dst
    first = np.ones(len(pair), dtype=bool)
    first[1:] = pair[1:] != pair[:-1]
    return src[first], dst[first], lat[first], loss[first]


def compute_all_pairs(g: Graph):
    """All-pairs (latency_ms, reliability) with reference semantics:
    Dijkstra over the minimum-latency edges, reliability accumulated
    along the same shortest path."""
    V = g.num_vertices
    src, dst, lat, loss = _build_adjacency(g)
    off = src != dst
    adj = csr_matrix((lat[off], (src[off], dst[off])), shape=(V, V))

    # Dijkstra with predecessors so reliability can be accumulated along
    # the same shortest path the latency uses.
    dist, pred = dijkstra(adj, directed=True, return_predecessors=True)

    # Edge loss lookup as dense [V, V] (PoI graphs are small: the bundled
    # topologies have <= a few thousand vertices).
    edge_loss = np.zeros((V, V))
    edge_has = np.zeros((V, V), dtype=bool)
    edge_loss[src, dst] = loss
    edge_has[src, dst] = True

    vloss = g.v_packetloss
    rel = np.ones((V, V))
    # Accumulate reliability along the shortest-path tree of each source:
    # process destinations in order of increasing distance so the
    # predecessor's reliability is already final.
    for s in range(V):
        order = np.argsort(dist[s], kind="stable")
        r = rel[s]
        r[:] = 0.0
        r[s] = 1.0 - vloss[s]
        for v in order:
            p = pred[s, v]
            if v == s or p < 0:
                continue
            r[v] = r[p] * (1.0 - edge_loss[p, v])
        # dst vertex loss applies once for distinct vertices
        r *= np.where(np.arange(V) == s, 1.0, 1.0 - vloss)

    lat_ms = dist.copy()
    # Same-vertex pairs: self-loop edge if present, else the reference's
    # 1 ms empty-path fallback; reliability from src vertex + self-loop.
    for v in range(V):
        if edge_has[v, v]:
            lat_ms[v, v] = lat[(src == v) & (dst == v)][0]
            rel[v, v] = (1.0 - vloss[v]) * (1.0 - edge_loss[v, v])
        else:
            lat_ms[v, v] = 1.0
            rel[v, v] = 1.0 - vloss[v]

    unreachable = ~np.isfinite(lat_ms)
    lat_ms[unreachable] = 0.0
    rel[unreachable] = 0.0
    # Reference clamps zero-latency paths up to 1 ms (shd-topology.c:760-766).
    lat_ms[(lat_ms <= 0.0) & ~unreachable] = 1.0
    return lat_ms, rel, unreachable


def build_topology(source) -> Topology:
    """Build a Topology from GraphML text/path or a parsed Graph."""
    g = source if isinstance(source, Graph) else parse_graphml(source)
    lat_ms, rel, unreachable = compute_all_pairs(g)
    lat_ns = np.round(lat_ms * SIMTIME_ONE_MILLISECOND).astype(np.int64)
    reachable = lat_ns[~unreachable]
    min_lat = int(reachable.min()) if reachable.size else 0
    return Topology(
        graph=g,
        latency_ns=lat_ns,
        reliability=rel.astype(np.float32),
        min_latency_ns=min_lat,
        v_bw_up_bytes=(g.v_bw_up * 1024).astype(np.int64),
        v_bw_down_bytes=(g.v_bw_down * 1024).astype(np.int64),
    )


# --- Host attachment -------------------------------------------------------
#
# Mirrors the reference's hint-driven placement
# (shd-topology.c:1071-1294): each host supplies optional ip / geocode /
# type hints; candidate vertices are scored, ip hints use
# longest-prefix-match, and ties break deterministically via the seeded
# per-host RNG rather than wall-clock randomness.

def _ip_to_int(s: str):
    try:
        return int(ipaddress.IPv4Address(s))
    except Exception:
        return None


def attach_hosts(topo: Topology, hints, seed: int = 1) -> np.ndarray:
    """Assign each host a vertex index.

    ``hints`` is a sequence of (ip_hint, geocode_hint, type_hint) tuples,
    one per host. Returns int32 [num_hosts] vertex indices.
    """
    g = topo.graph
    V = g.num_vertices
    vips = np.array([(_ip_to_int(ip) or -1) for ip in g.v_ip], dtype=np.int64)
    rng = np.random.RandomState(seed ^ 0x5EED)
    out = np.zeros(len(hints), dtype=np.int32)
    for i, (ip_hint, geo_hint, type_hint) in enumerate(hints):
        cand = np.ones(V, dtype=bool)
        if type_hint:
            m = np.array([t == type_hint for t in g.v_type])
            if m.any():
                cand &= m
        if geo_hint:
            m = np.array([c == geo_hint for c in g.v_geocode])
            if (cand & m).any():
                cand &= m
        idxs = np.flatnonzero(cand)
        if ip_hint:
            ip = _ip_to_int(ip_hint)
            if ip is not None:
                # longest common prefix with candidate vertex IPs
                valid = idxs[vips[idxs] >= 0]
                if valid.size:
                    xor = (vips[valid] ^ ip).astype(np.uint64)
                    # fewer leading-one bits in xor = longer shared prefix
                    prefix = 32 - np.ceil(np.log2(xor + 1)).astype(int)
                    best = prefix.max()
                    idxs = valid[prefix == best]
        out[i] = idxs[rng.randint(len(idxs))] if len(idxs) else rng.randint(V)
    return out
