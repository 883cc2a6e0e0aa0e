"""Copied from the system under test (routing/dns.py) so that the plain
reference imports nothing of the program."""

from __future__ import annotations

import ipaddress

import numpy as np

_RESERVED = [
    ipaddress.ip_network(n) for n in (
        "10.0.0.0/8", "100.64.0.0/10", "127.0.0.0/8", "169.254.0.0/16",
        "172.16.0.0/12", "192.0.0.0/24", "192.0.2.0/24", "192.88.99.0/24",
        "192.168.0.0/16", "198.18.0.0/15", "198.51.100.0/24",
        "203.0.113.0/24", "224.0.0.0/4", "240.0.0.0/4", "255.255.255.255/32",
    )
]


def _is_reserved(ip_int: int) -> bool:
    addr = ipaddress.IPv4Address(ip_int)
    return any(addr in net for net in _RESERVED)


class DNS:
    """Name/IP registry. Host ids are dense [0, H)."""

    def __init__(self):
        self._name_to_host = {}
        self._host_to_name = {}
        self._ip_to_host = {}
        self._host_to_ip = {}
        self._next_ip = int(ipaddress.IPv4Address("11.0.0.0"))

    def register(self, host_id: int, name: str, ip_hint: str = None) -> int:
        """Register a host; returns its assigned IPv4 as an int."""
        if name in self._name_to_host:
            raise ValueError(f"duplicate hostname {name!r}")
        ip = None
        if ip_hint:
            try:
                cand = int(ipaddress.IPv4Address(ip_hint))
                if cand not in self._ip_to_host and not _is_reserved(cand):
                    ip = cand
            except ipaddress.AddressValueError:
                ip = None
        if ip is None:
            while _is_reserved(self._next_ip) or self._next_ip in self._ip_to_host:
                self._next_ip += 1
            ip = self._next_ip
            self._next_ip += 1
        self._name_to_host[name] = host_id
        self._host_to_name[host_id] = name
        self._ip_to_host[ip] = host_id
        self._host_to_ip[host_id] = ip
        return ip

    def resolve(self, name: str) -> int:
        """Name -> host id (the virtual getaddrinfo)."""
        if name in self._name_to_host:
            return self._name_to_host[name]
        # dotted-quad literals resolve through the ip table
        try:
            ip = int(ipaddress.IPv4Address(name))
            return self._ip_to_host[ip]
        except (ipaddress.AddressValueError, KeyError):
            raise KeyError(f"unknown hostname {name!r}") from None

    def reverse(self, host_id: int) -> str:
        return self._host_to_name[host_id]

    def ip_of(self, host_id: int) -> int:
        return self._host_to_ip[host_id]

    def ip_str(self, host_id: int) -> str:
        return str(ipaddress.IPv4Address(self._host_to_ip[host_id]))

    def ip_array(self, num_hosts: int) -> np.ndarray:
        """[H] uint32 host id -> ip for device-side use (pcap, tracing)."""
        out = np.zeros(num_hosts, dtype=np.uint32)
        for h, ip in self._host_to_ip.items():
            if h < num_hosts:
                out[h] = ip
        return out
