"""Copied from the system under test (apps/compile.py) so that the plain
reference imports nothing of the program.

The process-spec compiler only."""

from __future__ import annotations

import os

import numpy as np

from .simtime import parse_time
from .wire import (APP_PING, APP_PING_SERVER, APP_PHOLD, APP_TGEN, APP_GOSSIP,
                   APP_BULK, APP_BULK_SERVER, APP_HOSTED,
                   APP_SOCKS_CLIENT, APP_SOCKS_PROXY)


def parse_kv(args: str) -> dict:
    out = {}
    for tok in args.split():
        if "=" in tok:
            k, v = tok.split("=", 1)
            out[k] = v
        else:
            out.setdefault("_positional", []).append(tok)
    return out


def compile_app(plugin: str, args: str, dns, num_hosts: int,
                tgen_tables=None):
    """-> (app_kind, cfg[8] int64) for one process spec."""
    cfg = np.zeros(8, dtype=np.int64)
    kv = parse_kv(args)
    if plugin == "ping":
        cfg[0] = dns.resolve(kv["peer"])
        cfg[1] = int(kv.get("port", 8000))
        cfg[2] = parse_time(kv.get("interval", "1s"))
        cfg[3] = int(kv.get("size", 64))
        cfg[4] = int(kv.get("count", 0))
        return APP_PING, cfg
    if plugin == "pingserver":
        cfg[1] = int(kv.get("port", 8000))
        return APP_PING_SERVER, cfg
    if plugin == "phold":
        cfg[0] = num_hosts
        cfg[1] = int(kv.get("port", 8000))
        cfg[2] = parse_time(kv.get("mean", "100ms"))
        cfg[3] = int(kv.get("size", 64))
        cfg[4] = int(kv.get("init", 1))
        return APP_PHOLD, cfg
    if plugin == "bulk":
        cfg[0] = dns.resolve(kv["peer"])
        cfg[1] = int(kv.get("port", 80))
        cfg[2] = int(kv.get("size", 1 << 20))
        cfg[3] = int(kv.get("count", 1))
        cfg[4] = parse_time(kv.get("pause", "1s"))
        return APP_BULK, cfg
    if plugin == "bulkserver":
        cfg[1] = int(kv.get("port", 80))
        return APP_BULK_SERVER, cfg
    if plugin == "gossip":
        # block-gossip / Bitcoin-style tip propagation (apps/gossip.py).
        # `n` bounds the peer id range for relay draws; it defaults to
        # the whole scenario — in MIXED scenarios set n to the gossip
        # host count and put the gossip hosts first, or a share of
        # relays target non-gossip hosts and silently vanish.
        cfg[0] = int(kv.get("n", num_hosts))
        cfg[1] = int(kv.get("port", 8333))
        cfg[2] = int(kv.get("fanout", 8))
        cfg[3] = parse_time(kv.get("interval", "10s"))
        cfg[4] = int(kv.get("miner", 0))
        cfg[5] = int(kv.get("size", 500))
        return APP_GOSSIP, cfg
    if plugin == "socksclient":
        # proxy-chain fetch client (apps/socks.py). Host-id ranges name
        # the proxy and server pools (hosts are id-ordered by their
        # declaration order in the scenario).
        cfg[0] = int(kv["proxy-lo"])
        cfg[1] = int(kv["proxy-hi"])
        cfg[2] = int(kv.get("proxy-port", 9050))
        cfg[3] = int(kv["server-lo"])
        cfg[4] = int(kv["server-hi"])
        if cfg[4] - 1 > 0xFFFFF:
            # only server ids ride the 20-bit CONNECT-tag host field
            # (relay hops are dialed directly, not packed)
            raise ValueError(
                "socksclient server host ids exceed the 20-bit "
                "CONNECT-tag field (max id 1048575)")
        # sizes round UP to the tag's 4 KiB units (never under-deliver)
        size_u4k = max(1, (int(kv.get("size", 51200)) + 4095) >> 12)
        if size_u4k > 0x1FF:
            # the SYN-tag CONNECT encoding carries 9 bits of 4KiB units
            raise ValueError(
                f"socksclient size {kv.get('size')} exceeds the "
                "~2 MiB per-fetch limit of the tag encoding")
        cfg[5] = size_u4k
        cfg[6] = int(kv.get("count", 0))
        hops = int(kv.get("hops", 1))
        if not 1 <= hops <= 3:
            raise ValueError("socksclient hops must be 1-3 "
                             "(relays per circuit)")
        cfg[7] = parse_time(kv.get("pause", "1s")) | (hops << 56)
        return APP_SOCKS_CLIENT, cfg
    if plugin == "socksproxy":
        cfg[1] = int(kv.get("port", 9050))
        cfg[2] = int(kv.get("server-port", 80))
        # relay pool for multi-hop circuit extension (0,0 = none).
        # Chain extension dials the next relay on THIS relay's own
        # port= value, so every relay in one pool must listen on the
        # same port.
        cfg[3] = int(kv.get("relay-lo", 0))
        cfg[4] = int(kv.get("relay-hi", 0))
        return APP_SOCKS_PROXY, cfg
    if plugin.startswith("hosted:"):
        # CPU-hosted real app code (hosting/): the Simulation builds a
        # HostingRuntime instance per such host; nothing device-side to
        # compile beyond the wake-ring app kind.
        return APP_HOSTED, cfg
    if plugin == "tgen":
        if tgen_tables is None:
            raise ValueError("tgen requires a TgenTables compile context")
        source = args.strip()
        if not source.startswith("<"):
            # not inline graphml: a file path (the reference's argv
            # form). Use the raw argument string, not parse_kv's
            # key=value splitting (paths may contain '=').
            if not source:
                raise ValueError(
                    "tgen requires a behavior graph (a graphml path or "
                    "inline graphml) as its process argument")
            if not os.path.exists(source):
                raise ValueError(
                    f"tgen behavior graph not found: {source!r}")
        cfg[0] = tgen_tables.compile(source, dns)
        return APP_TGEN, cfg
    raise ValueError(f"unknown plugin {plugin!r} "
                     "(builtin: ping, pingserver, phold, bulk, bulkserver, "
                     "tgen, gossip, socksclient, socksproxy)")
