"""Profiler trace -> device busy time and the breakdown.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes (through
``jax.profiler.ProfileData``, nothing of the program). Device planes
are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation run.
Host planes (``/host:...``) hold the harness's ``TraceAnnotation``
spans and the runtime's own host events.

- busy: the union of operation intervals on a device, inside the
  traced slice; averaged over the devices used.
- device_ops: operation self-time (less the operations nested in it),
  grouped by HLO instruction kind.
- idle_gaps: the longest stretches with no operation on device 0,
  each named by the innermost host event covering its midpoint.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals):
    """Merge [start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_label(ev) -> str:
    """The kind of an operation's HLO instruction: ``%fusion.123 = ...``
    -> ``fusion`` (the trace records no scope path for TPU ops)."""
    name = ev.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def self_times(events):
    """(event, its duration less that of the events nested in it) for
    the events of one line, which nest as a stack."""
    out, stack = [], []       # stack of [event, self_ns]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, e.end_ns - e.start_ns])
    out.extend(tuple(s) for s in reversed(stack))
    return out


class Trace:
    """The planes of one trace, in plain lists."""

    def __init__(self, planes):
        # planes: [(name, [(line name, [event])])]
        self.devices = {}
        self.host = []
        for pname, lines in planes:
            m = DEVICE_PLANE.match(pname)
            if m:
                self.devices[int(m.group(1))] = dict(lines)
            elif pname.startswith("/host:"):
                for _, evs in lines:
                    self.host.extend(evs)

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            path = find_xplane(path)
        pd = ProfileData.from_file(path)
        return cls.from_profile(pd)

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        planes = []
        for p in pd.planes:
            planes.append((p.name, [(ln.name, list(ln.events))
                                    for ln in p.lines]))
        return cls(planes)

    def device_ids(self):
        return sorted(self.devices)

    def ops(self, dev):
        lines = self.devices.get(dev, {})
        return lines.get(OPS_LINE, [])

    def busy_ns(self, lo, hi, devices=None):
        """Union of operation time inside [lo, hi), averaged over the
        devices (all traced devices by default)."""
        devs = devices if devices is not None else self.device_ids()
        if not devs:
            return 0.0
        tot = 0
        for d in devs:
            iv = union(clip([(e.start_ns, e.end_ns) for e in self.ops(d)],
                            lo, hi))
            tot += sum(e - s for s, e in iv)
        return tot / len(devs)

    def device_ops(self, lo, hi, dev=0, top=10):
        """Operation self-time inside [lo, hi) by op_label, largest
        first: [[label, seconds]]."""
        acc = {}
        for e, own in self_times(self.ops(dev)):
            if e.start_ns >= lo and e.end_ns <= hi:
                k = op_label(e)
                acc[k] = acc.get(k, 0) + own
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / 1e9] for k, v in rows]

    def idle_gaps(self, lo, hi, dev=0, top=10):
        busy = union(clip([(e.start_ns, e.end_ns) for e in self.ops(dev)],
                          lo, hi))
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for s, e in gaps[:top]:
            out.append([self.host_at((s + e) // 2), (e - s) / 1e9])
        return out

    def host_at(self, t_ns) -> str:
        """Innermost (shortest) host event covering `t_ns`."""
        best = None
        for e in self.host:
            if e.start_ns <= t_ns < e.end_ns:
                if best is None or e.duration_ns < best.duration_ns:
                    best = e
        return best.name if best is not None else "no host event"

    def first_start(self):
        """The start of the earliest event on any plane."""
        starts = [e.start_ns for e in self.host]
        for d in self.device_ids():
            for evs in self.devices[d].values():
                starts.extend(e.start_ns for e in evs)
        return min(starts)

    def last_end(self):
        """The end of the latest event on any plane: where the profiler
        stopped."""
        ends = [e.end_ns for e in self.host]
        for d in self.device_ids():
            for evs in self.devices[d].values():
                ends.extend(e.end_ns for e in evs)
        return max(ends)
