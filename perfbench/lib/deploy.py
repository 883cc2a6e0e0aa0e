"""The one traffic generator: a configuration file and a traffic file
-> a plain deployment description.

The description is plain data (dicts, lists, strings, ints), handed to
the system under test (lib/system.py turns it into the program's
Scenario) and, unchanged, to the plain reference (reference/), which
never sees what the program builds from it.

Configuration (configs/<name>.json): host count, roles in declaration
order (share of hosts or "rest", plugin, deployment arguments), the
topology, engine caps, runahead, stop time and ``layout_seed``.
Traffic (traffic/<name>.json): per role, the process start time, extra
arguments and the tgen walk. Argument templates may name a role's
host-id range, ``{lo:relay}`` and ``{hi:relay}`` (half-open), or the
one host of a role that this host is pinned to, ``{pin:relay:lo}`` and
``{pin:relay:hi}`` (a range of one).

The layout is fixed by the configuration's ``layout_seed``: the vertex
each host sits on (a geocode hint that names one vertex) and the host
each pin names. The run's seed only permutes which host id plays which
part within each role, and seeds the apps' own draws: every seed gives
the same set of paths and transfers, in another order, so the work of
a run does not change with the seed. The same seed gives the same
deployment.
"""

from __future__ import annotations

import random
import re

NS_PER_S = 10**9

# --- topology: connected Erdos-Renyi graph, GraphML text -----------------

_GRAPHML_HEAD = """<?xml version="1.0" encoding="utf-8"?>
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="packetloss" attr.type="double" for="edge" id="d9" />
  <key attr.name="latency" attr.type="double" for="edge" id="d7" />
  <key attr.name="type" attr.type="string" for="node" id="d5" />
  <key attr.name="bandwidthup" attr.type="int" for="node" id="d4" />
  <key attr.name="bandwidthdown" attr.type="int" for="node" id="d3" />
  <key attr.name="geocode" attr.type="string" for="node" id="d1" />
  <key attr.name="packetloss" attr.type="double" for="node" id="d0" />
  <graph edgedefault="undirected">"""


def vertex_geocode(i: int) -> str:
    """The geocode that names vertex `i` alone."""
    return f"v{i}"


def erdos_renyi(vertices, edge_p, seed, latency_ms_range, self_loop_ms,
                bandwidth_kib_s, loss):
    """A random graph plus a spanning tree (so it is connected), with
    self-loops of `self_loop_ms`; one uniform latency draw per edge.
    Each vertex has a geocode of its own. Same seed, same graph."""
    rng = random.Random(seed)
    lo, hi = latency_ms_range
    lines = [_GRAPHML_HEAD]
    for i in range(vertices):
        lines.append(f'    <node id="poi-{i}"><data key="d0">0.0</data>'
                     f'<data key="d1">{vertex_geocode(i)}</data>'
                     f'<data key="d3">{bandwidth_kib_s}</data>'
                     f'<data key="d4">{bandwidth_kib_s}</data>'
                     f'<data key="d5">net</data></node>')

    def edge(a, b, lat, ls):
        lines.append(f'    <edge source="poi-{a}" target="poi-{b}">'
                     f'<data key="d7">{lat}</data>'
                     f'<data key="d9">{ls}</data></edge>')

    for i in range(vertices):
        edge(i, i, self_loop_ms, 0.0)
    for i in range(1, vertices):
        edge(rng.randrange(i), i, round(rng.uniform(lo, hi), 2), loss)
    for a in range(vertices):
        for b in range(a + 1, vertices):
            if rng.random() < edge_p:
                edge(a, b, round(rng.uniform(lo, hi), 2), loss)
    lines.append("  </graph>\n</graphml>")
    return "\n".join(lines)


TOPOLOGIES = {"erdos_renyi": erdos_renyi}

# --- tgen behaviour graphs ------------------------------------------------

_TGEN_KEYS = (
    '<key attr.name="count" attr.type="string" for="node" id="d6"/>'
    '<key attr.name="size" attr.type="string" for="node" id="d5"/>'
    '<key attr.name="type" attr.type="string" for="node" id="d4"/>'
    '<key attr.name="time" attr.type="string" for="node" id="d2"/>'
    '<key attr.name="peers" attr.type="string" for="node" id="d0"/>'
    '<key attr.name="serverport" attr.type="string" for="node" id="d1"/>')


def tgen_listen(port):
    return ('<graphml xmlns="http://graphml.graphdrawing.org/xmlns">'
            f'{_TGEN_KEYS}<graph edgedefault="directed">'
            f'<node id="start"><data key="d1">{port}</data></node>'
            '</graph></graphml>')


def tgen_walk(peers, type, size, pause, count):
    """start -> transfer -> end(count) -> pause -> start."""
    return ('<graphml xmlns="http://graphml.graphdrawing.org/xmlns">'
            f'{_TGEN_KEYS}<graph edgedefault="directed">'
            f'<node id="start"><data key="d0">{peers}</data></node>'
            f'<node id="pause"><data key="d2">{pause}</data></node>'
            '<node id="transfer">'
            f'<data key="d4">{type}</data><data key="d5">{size}</data></node>'
            f'<node id="end"><data key="d6">{count}</data></node>'
            '<edge source="start" target="transfer"/>'
            '<edge source="transfer" target="end"/>'
            '<edge source="end" target="pause"/>'
            '<edge source="pause" target="start"/>'
            '</graph></graphml>')


# --- the deployment -------------------------------------------------------

def role_counts(hosts, roles):
    """Hosts per role: max(int(hosts * share), 1) for each share, the
    rest to the one role whose share is "rest"."""
    counts = {}
    for r in roles:
        if r["share"] != "rest":
            counts[r["id"]] = max(int(hosts * r["share"]), 1)
    rest = [r["id"] for r in roles if r["share"] == "rest"]
    if len(rest) != 1:
        raise ValueError("exactly one role takes the rest of the hosts")
    counts[rest[0]] = hosts - sum(counts.values())
    if counts[rest[0]] < 1:
        raise ValueError("no hosts left for the rest role")
    return counts


def host_names(role_id, n):
    """The simulator's naming: ``web1``.. for a role of several hosts."""
    return [role_id] if n == 1 else [f"{role_id}{q + 1}" for q in range(n)]


def _peers(spec, ranges, counts):
    role, _, port = spec.partition(":")
    names = host_names(role, counts[role])
    return ",".join(f"{n}:{port}" for n in names)


_RANGE = re.compile(r"\{(lo|hi):([A-Za-z0-9_.-]+)\}")
_PIN = re.compile(r"\{pin:([A-Za-z0-9_.-]+):(lo|hi)\}")


def _fill(template, ranges, pinned):
    """Fill range templates from `ranges` and pin templates from
    `pinned` (role -> the pinned host's id)."""
    def rng(m):
        lo, hi = ranges[m.group(2)]
        return str(lo if m.group(1) == "lo" else hi)

    def pin(m):
        h = pinned[m.group(1)]
        return str(h if m.group(2) == "lo" else h + 1)
    return _PIN.sub(pin, _RANGE.sub(rng, template))


def _args(role, t, ranges, counts):
    """-> the role's process arguments, still holding pin templates."""
    tg = dict(role.get("tgen", {}), **t.get("tgen", {}))
    if "listen" in tg:
        return tgen_listen(tg["listen"])
    if "walk" in tg:
        w = dict(tg["walk"])
        w["peers"] = _peers(w["peers"], ranges, counts)
        return tgen_walk(**w)
    return " ".join(a for a in (role.get("args", ""), t.get("args", ""))
                    if a)


def build(config: dict, traffic: dict, seed: int) -> dict:
    """-> the plain deployment description (module doc): one host entry
    per host, in host-id order."""
    roles = config["roles"]
    counts = role_counts(config["hosts"], roles)
    ranges, lo = {}, 0
    for r in roles:
        ranges[r["id"]] = (lo, lo + counts[r["id"]])
        lo += counts[r["id"]]
    topo = dict(config["topology"])
    kind = topo.pop("kind")
    procs = {}
    for r in roles:
        t = traffic["roles"].get(r["id"])
        if t is None:
            raise ValueError(f"traffic {traffic['name']!r} gives role "
                             f"{r['id']!r} no process")
        procs[r["id"]] = (int(round(t["start_s"] * NS_PER_S)),
                          _args(r, t, ranges, counts))

    # the layout, from layout_seed alone: first every host's vertex,
    # role by role, then each role's pins, target by target in the
    # order the arguments name them
    draw = random.Random(config["layout_seed"])
    vertex = {r["id"]: [draw.randrange(topo["vertices"])
                        for _ in range(counts[r["id"]])] for r in roles}
    picks = {}
    for r in roles:
        targets = dict.fromkeys(m.group(1) for m in
                                _PIN.finditer(procs[r["id"]][1]))
        picks[r["id"]] = {x: [draw.randrange(counts[x])
                              for _ in range(counts[r["id"]])]
                          for x in targets}

    # the seed's permutation: place[role][part] is the position within
    # the role of the host that plays that part
    perm = random.Random(int(seed))
    place = {}
    for r in roles:
        p = list(range(counts[r["id"]]))
        perm.shuffle(p)
        place[r["id"]] = p

    hosts = []
    for r in roles:
        rid = r["id"]
        part_at = {pos: part for part, pos in enumerate(place[rid])}
        start_ns, template = procs[rid]
        for pos, name in enumerate(host_names(rid, counts[rid])):
            part = part_at[pos]
            pinned = {x: ranges[x][0] + place[x][picks[rid][x][part]]
                      for x in picks[rid]}
            hosts.append({
                "id": name, "quantity": 1,
                "geocode": vertex_geocode(vertex[rid][part]),
                "processes": [{"plugin": r["plugin"], "start_ns": start_ns,
                               "args": _fill(template, ranges, pinned)}]})
    return {
        "config": config["name"], "traffic": traffic["name"],
        "seed": int(seed) % 2**32,
        "stop_ns": int(round(config["stop_s"] * NS_PER_S)),
        "runahead_ns": int(config["runahead_ms"]) * 10**6,
        "caps": dict(config["engine_caps"]),
        "topology_graphml": TOPOLOGIES[kind](**topo),
        "hosts": hosts,
    }
