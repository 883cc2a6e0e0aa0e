"""Copied from the system under test (routing/graphml.py) so that the plain
reference imports nothing of the program."""

from __future__ import annotations

import lzma
import os
from dataclasses import dataclass, field
from xml.etree import ElementTree

import numpy as np

_NS = "{http://graphml.graphdrawing.org/xmlns}"


@dataclass
class Graph:
    """Parsed topology graph (vertices = points of interest)."""
    vertex_ids: list                 # string ids, index = vertex index
    directed: bool
    # vertex attributes (parallel arrays, len V)
    v_ip: list = field(default_factory=list)          # strings (may be "0.0.0.0")
    v_geocode: list = field(default_factory=list)
    v_type: list = field(default_factory=list)
    v_asn: np.ndarray = None
    v_bw_up: np.ndarray = None       # KiB/s as in the graphml
    v_bw_down: np.ndarray = None
    v_packetloss: np.ndarray = None
    # edges (E rows)
    e_src: np.ndarray = None
    e_dst: np.ndarray = None
    e_latency_ms: np.ndarray = None
    e_jitter_ms: np.ndarray = None
    e_packetloss: np.ndarray = None

    @property
    def num_vertices(self) -> int:
        return len(self.vertex_ids)

    @property
    def num_edges(self) -> int:
        return 0 if self.e_src is None else len(self.e_src)


def _read_text(source: str) -> str:
    if "\n" not in source and os.path.exists(source):
        if source.endswith(".xz"):
            with lzma.open(source, "rt") as f:
                return f.read()
        with open(source) as f:
            return f.read()
    return source


def _strip(tag: str) -> str:
    return tag.split("}")[-1]


def parse_graphml(source: str) -> Graph:
    """Parse GraphML text or a file path (optionally .xz) into a Graph."""
    text = _read_text(source)
    root = ElementTree.fromstring(text)

    # key id -> (domain, attr name, attr type)
    keys = {}
    graph_el = None
    for el in root:
        tag = _strip(el.tag)
        if tag == "key":
            keys[el.attrib["id"]] = (
                el.attrib.get("for", "node"),
                el.attrib.get("attr.name", el.attrib["id"]),
                el.attrib.get("attr.type", "string"),
            )
        elif tag == "graph":
            graph_el = el
    if graph_el is None:
        raise ValueError("graphml contains no <graph> element")
    directed = graph_el.attrib.get("edgedefault", "undirected") == "directed"

    def data_of(el):
        out = {}
        for d in el:
            if _strip(d.tag) == "data":
                _, name, _ = keys.get(d.attrib["key"], (None, d.attrib["key"], "string"))
                out[name] = (d.text or "").strip()
        return out

    vertex_ids, vdata = [], []
    edges = []
    for el in graph_el:
        tag = _strip(el.tag)
        if tag == "node":
            vertex_ids.append(el.attrib["id"])
            vdata.append(data_of(el))
        elif tag == "edge":
            edges.append((el.attrib["source"], el.attrib["target"], data_of(el)))

    vindex = {vid: i for i, vid in enumerate(vertex_ids)}
    V, E = len(vertex_ids), len(edges)

    g = Graph(vertex_ids=vertex_ids, directed=directed)
    g.v_ip = [d.get("ip", "") for d in vdata]
    g.v_geocode = [d.get("geocode", "") for d in vdata]
    g.v_type = [d.get("type", "") for d in vdata]
    g.v_asn = np.array([int(d.get("asn", 0) or 0) for d in vdata], dtype=np.int64)
    g.v_bw_up = np.array([float(d.get("bandwidthup", 0) or 0) for d in vdata])
    g.v_bw_down = np.array([float(d.get("bandwidthdown", 0) or 0) for d in vdata])
    g.v_packetloss = np.array([float(d.get("packetloss", 0) or 0) for d in vdata])

    g.e_src = np.array([vindex[s] for s, _, _ in edges], dtype=np.int64)
    g.e_dst = np.array([vindex[t] for _, t, _ in edges], dtype=np.int64)
    g.e_latency_ms = np.array([float(d.get("latency", 0) or 0) for _, _, d in edges])
    g.e_jitter_ms = np.array([float(d.get("jitter", 0) or 0) for _, _, d in edges])
    g.e_packetloss = np.array([float(d.get("packetloss", 0) or 0) for _, _, d in edges])

    # Validate like the reference (shd-topology.c:232-474): latencies must be
    # positive on every edge.
    if E and (g.e_latency_ms <= 0).any():
        bad = int(np.argmax(g.e_latency_ms <= 0))
        raise ValueError(
            f"invalid latency {g.e_latency_ms[bad]} on edge "
            f"{vertex_ids[g.e_src[bad]]}->{vertex_ids[g.e_dst[bad]]}")
    return g
