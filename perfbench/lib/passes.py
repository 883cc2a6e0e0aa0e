"""Device operation self-time of the traced slice by pass label.

The slice's operation events carry HLO instruction names only (the
profiler runs without HLO protos, lib/trace.py). The program supplies
one thing: the map from those names to each op's ``op_name`` metadata,
per HLO module, read from its own executables
(``shadow_tpu.engine.window.chunk_program_scopes``). The modules that
ran in the slice (the device's ``XLA Modules`` line) pick the maps,
since instruction names repeat across modules; where none of them is
named there, one module's map is taken alone and several give nothing.
The label rules are the benchmark's own, here, so a change to the
program's profiler tools cannot move these metrics: transform wrappers such as
``vmap(tcp.rx)`` are peeled, the innermost label on the path wins, and
a compaction rung with no label inside it is the drain's. A program
without that map gives nothing.
"""

from __future__ import annotations

import re
import sys
import time

from perfbench.lib.trace import self_times

MODULES_LINE = "XLA Modules"
DRAIN = ("drain",)
HANDLERS = ("tcp.rx", "tcp.timer", "udp.deliver", "nic.tx", "nic.rx_admit")
EXCHANGE = ("exchange", "exchange.sharded", "cap_peaks", "advance")
LABELS = frozenset(DRAIN + HANDLERS + EXCHANGE)
# the drain's compaction rungs: w<K> window rungs, k<K> per-pass
# rungs, dense
RUNG = re.compile(r"^(?:[wk][0-9]+|dense)$")
# a scope entered under a transform: ``vmap(nic.tx)``, ``vmap(jit(f))``
WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def op_name(ev) -> str:
    """``%fusion.123 = ...`` or ``fusion.123`` -> ``fusion.123``."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def label_of(path: str | None) -> str | None:
    """The pass label of an op whose ``op_name`` is `path`, or None."""
    rung = False
    for part in reversed((path or "").split("/")):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        if part in LABELS:
            return part
        rung = rung or bool(RUNG.match(part))
    return "drain" if rung else None


def program_scopes():
    """{module name: {HLO instruction name: op_name}} of the program's
    window-chunk programs, or None where the program has no such map."""
    try:
        from shadow_tpu.engine.window import chunk_program_scopes
    except ImportError:
        return None
    return chunk_program_scopes() or None


def slice_scopes(tr, lo, hi, maps):
    """-> (module names, {HLO instruction name: op_name}) of the
    modules that ran on device 0 inside [lo, hi), or None (see the
    module's docstring)."""
    ran = {e.name.split("(", 1)[0]
           for e in tr.devices.get(0, {}).get(MODULES_LINE, [])
           if e.end_ns > lo and e.start_ns < hi}
    picked = sorted(name for name in maps if name in ran)
    if not picked and len(maps) == 1:
        picked = list(maps)
    if not picked:
        return None
    out = {}
    for name in picked:
        out.update(maps[name])
    return picked, out


def attribution(ctx):
    """{"ns": {label: self-time}, "total_ns", "labelled_frac"} over
    device 0's operations inside the slice, or None. `ctx["scopes"]`,
    where given, stands for the program's maps. Computed once per ctx;
    the labelled share is said on stderr."""
    if "_passes" in ctx:
        return ctx["_passes"]
    ctx["_passes"] = None
    tr = ctx.get("trace")
    if tr is None:
        return None
    t = time.perf_counter()
    maps = ctx.get("scopes")
    if maps is None:
        maps = program_scopes()
    if not maps:
        return None
    lo, hi = ctx["trace_lo"], ctx["trace_hi"]
    picked = slice_scopes(tr, lo, hi, maps)
    if picked is None:
        return None
    modules, scopes = picked
    ns, total, unlabelled = {}, 0, {}
    for e, own in self_times(tr.ops(0)):
        if e.start_ns >= lo and e.end_ns <= hi:
            k = op_name(e)
            total += own
            label = label_of(scopes.get(k))
            if label is None:
                unlabelled[k] = unlabelled.get(k, 0) + own
            else:
                ns[label] = ns.get(label, 0) + own
    if not total:
        return None
    a = {"ns": ns, "total_ns": total,
         "labelled_frac": sum(ns.values()) / total}
    ctx["_passes"] = a
    top = sorted(unlabelled.items(), key=lambda kv: -kv[1])[:4]
    print(f"perfbench: pass labels on {100.0 * a['labelled_frac']:.2f}% "
          f"of {total / 1e6:.3f} ms of device operation self-time in "
          f"the slice ({len(scopes)} HLO names of {modules} mapped in "
          f"{time.perf_counter() - t:.3f} s); ms by label: "
          f"{ {k: round(v / 1e6, 3) for k, v in ns.items()} }; "
          f"unlabelled ms: { {k: round(v / 1e6, 3) for k, v in top} }",
          file=sys.stderr, flush=True)
    return a


def share(ctx, labels):
    """Percent of the slice's device operation self-time under
    `labels`, or None."""
    a = attribution(ctx)
    if a is None:
        return None
    return 100.0 * sum(a["ns"].get(k, 0) for k in labels) / a["total_ns"]
