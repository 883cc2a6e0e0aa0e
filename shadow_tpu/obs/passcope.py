"""passcope — per-pass device-time & lockstep-occupancy observatory.

The third observatory tier: obs.perf attributes host WALL time to
engine phases, obs.memscope attributes BYTES; this module attributes
the DEVICE time spent inside the compiled window program to the same
named passes the stateflow matrix analyzes (lint/stateflow.py
ENTRIES: drain / exchange / cap_peaks / advance / nic.tx /
nic.rx_admit / tcp.rx / tcp.timer / udp.deliver), and measures how
much of each lockstep pass was wasted on idle lanes — the two numbers
the conservative-lookahead design hides from host-side timing
(tools/xplane_profile.py's docstring: nothing finer than ~10 ms
resolves from outside the jitted program).

Three surfaces, mirroring the obs.perf contract:

- **Wire decoder** (`parse_xspace` / `hlo_scope_map` /
  `device_self_times`): the xplane protobuf decoder, promoted here
  from tools/xplane_profile.py (which is now a thin CLI over this
  module — one wire-format implementation). Beyond the per-op
  duration table the tool always printed, it decodes the serialized
  HloProto the profiler embeds in the ``/host:metadata`` plane and
  maps every HLO instruction to its `jax.named_scope` path, so
  device self-times land on pass labels, not HLO mangles.
- **Attribution** (`attribute`): per-op SELF time (stack walk over
  nested (offset, duration) intervals — a while-loop's span must not
  double-count its body) mapped to the INNERMOST pass label on the
  op's scope path. ≥90% of trace-window device time attributed
  (`MIN_ATTRIBUTED`) or the result flags itself and labels the
  residual — the PR 6 rule, applied to device time.
- **Occupancy** (`occupancy` / `shard_occupancy`): lockstep
  efficiency from data the drain already returns (the per-rung pass
  mix + executed events) — no extra device work, so it is always on.
  A pass over a rung of width W engages W lanes whether or not a
  host has work; `waste_frac` is the fraction of those lane-steps no
  event filled.

Import cost: stdlib only. jax is imported lazily inside `Capture`,
so the headless consumers (tools/xplane_profile.py --self-check, the
CI simlint job with no jax installed) load this file by path and pay
nothing.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re

# the stateflow entry names (lint/stateflow.py ENTRIES) this module
# attributes device time to — the same labels engine/window.py and
# parallel/shard.py stamp with jax.named_scope
PASS_LABELS = (
    "drain", "exchange", "exchange.sharded", "cap_peaks", "advance",
    "nic.tx", "nic.rx_admit", "tcp.rx", "tcp.timer", "udp.deliver",
)
# drain-rung sublabels (engine.window.pass_labels): w<K> window
# rungs, k<K> per-pass rungs, dense
_RUNG_RE = re.compile(r"^(?:[wk][0-9]+|dense)$")
# a scope entered under a transform is rendered inside it on the
# op_name path: ``vmap(tcp.rx)``, ``vmap(jit(_where))``
_WRAPPED_RE = re.compile(r"^[\w.]+\((.*)\)$")


def _scopes_of(op):
    """The scope names on an op_name path, transform wrappers peeled:
    the handlers run vmapped over the drain's lanes, so their labels
    appear as ``vmap(nic.tx)``."""
    out = []
    for part in op.split("/"):
        m = _WRAPPED_RE.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED_RE.match(part)
        out.append(part)
    return out

MIN_ATTRIBUTED = 0.90
RESIDUAL = "unattributed (device glue)"

DEFAULT_TRACE_CHUNKS = 8


# --- minimal protobuf wire decoding ---------------------------------------
# (the single implementation; tools/xplane_profile.py imports these)

def _varint(buf, i):
    x = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7


def _fields(buf):
    """Yield (field_number, wire_type, value) over a message buffer.
    value: int for varint(0)/fixed(1,5), memoryview for bytes(2)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fn, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:  # groups unsupported/absent in xplane
            raise ValueError(f"wire type {wt}")
        yield fn, wt, v


def parse_xspace(path):
    """-> [(plane_name, [(line_name, durs, counts)])] — the per-line
    duration aggregate tools/xplane_profile.py has always printed
    (byte-format of its report unchanged)."""
    buf = memoryview(open(path, "rb").read())
    planes = []
    for fn, wt, v in _fields(buf):
        if fn == 1 and wt == 2:             # XSpace.planes
            planes.append(_parse_plane(v))
    return planes


def _plane_raw(buf):
    """-> (name, {metadata_id: metadata_buf}, [line_buf])."""
    name = ""
    emeta = {}
    lines = []
    for fn, wt, v in _fields(buf):
        if fn == 2 and wt == 2:              # XPlane.name
            name = bytes(v).decode("utf-8", "replace")
        elif fn == 3 and wt == 2:            # XPlane.lines
            lines.append(v)
        elif fn == 4 and wt == 2:            # XPlane.event_metadata map
            k, m = None, None
            for fn2, wt2, v2 in _fields(v):
                if fn2 == 1:
                    k = v2
                elif fn2 == 2 and wt2 == 2:
                    m = v2
            if k is not None and m is not None:
                emeta[k] = m
    return name, emeta, lines


def _meta_name(mbuf):
    for fn, wt, v in _fields(mbuf):
        if fn == 2 and wt == 2:              # XEventMetadata.name
            return bytes(v).decode("utf-8", "replace")
    return ""


def _parse_plane(buf):
    name, emeta, lines = _plane_raw(buf)
    meta = {k: _meta_name(m) for k, m in emeta.items()}
    # Aggregate PER LINE: device traces nest container ops (module,
    # while, conditional) on separate lines above the leaf-op line, so
    # a single merged counter double-counts bodies inside containers
    # and conds "cost" their whole branch. Per-line tops let the
    # reader see both views: containers (where the window time sits
    # structurally) and leaves (which HLOs actually burn it).
    per_line = []                            # (line_name, durs, counts)
    for lbuf in lines:
        lname, evs = _line_events(lbuf)
        durs = collections.Counter()
        counts = collections.Counter()
        for _off, dur, mid in evs:
            key = meta.get(mid, f"#{mid}")
            durs[key] += dur
            counts[key] += 1
        if durs:
            per_line.append((lname, dict(durs), dict(counts)))
    return name, per_line


def _line_events(lbuf):
    """-> (line_name, [(offset_ps, duration_ps, metadata_id)])."""
    lname = ""
    evs = []
    for fn, wt, v in _fields(lbuf):
        if fn == 2 and wt == 2:              # XLine.name
            lname = bytes(v).decode("utf-8", "replace")
        # this build writes XLine.events at field 4 (older schema
        # revisions used 6 — accept both)
        elif fn in (4, 6) and wt == 2:       # XLine.events
            mid, off, dur = None, 0, 0
            for fn2, wt2, v2 in _fields(v):
                if fn2 == 1:                 # XEvent.metadata_id
                    mid = v2
                elif fn2 == 2:               # XEvent.offset_ps
                    off = v2
                elif fn2 == 3:               # XEvent.duration_ps
                    dur = v2
            if mid is not None:
                evs.append((off, dur, mid))
    return lname, evs


# --- HLO scope map: instruction name -> named_scope path ------------------

def _walk_hlo_module(mod):
    """HloModuleProto: f3 computations (ALL of them — while bodies and
    cond branches included) -> f2 instructions -> f1 name,
    f7 OpMetadata -> f2 op_name (the full scope path, e.g.
    ``jit(run_windows)/.../drain/w512/gather``)."""
    out = {}
    for fn, wt, v in _fields(mod):
        if fn == 3 and wt == 2:
            for fn2, wt2, v2 in _fields(v):
                if fn2 == 2 and wt2 == 2:
                    nm = op = None
                    for fn3, wt3, v3 in _fields(v2):
                        if fn3 == 1 and wt3 == 2:
                            nm = bytes(v3).decode("utf-8", "replace")
                        elif fn3 == 7 and wt3 == 2:
                            for fn4, wt4, v4 in _fields(v3):
                                if fn4 == 2 and wt4 == 2:
                                    op = bytes(v4).decode(
                                        "utf-8", "replace")
                    if nm:
                        out[nm] = op
    return out


def _module_name(mod):
    """HloModuleProto f1: the module's name."""
    for fn, wt, v in _fields(mod):
        if fn == 1 and wt == 2:
            return bytes(v).decode("utf-8", "replace")
    return None


def hlo_scope_map(path, module=None):
    """-> {hlo_instruction_name: op_name} over every module the
    profiler recorded (the ``/host:metadata`` plane embeds one
    serialized HloProto per jitted module the process holds —
    XEventMetadata stats carry it as the bytes value), or over the
    module named `module` alone: instruction names repeat across
    modules."""
    buf = memoryview(open(path, "rb").read())
    scopes = {}
    for fn, wt, v in _fields(buf):
        if fn != 1 or wt != 2:
            continue
        name, emeta, _lines = _plane_raw(v)
        if name != "/host:metadata":
            continue
        for m in emeta.values():
            for fn2, wt2, v2 in _fields(m):
                if fn2 == 5 and wt2 == 2:          # XEventMetadata.stats
                    for fn3, wt3, v3 in _fields(v2):
                        if fn3 == 6 and wt3 == 2:  # XStat.bytes_value
                            for fn4, wt4, v4 in _fields(v3):
                                if fn4 == 1 and wt4 == 2:  # HloProto.hlo_module
                                    if (module is None
                                            or _module_name(v4) == module):
                                        scopes.update(_walk_hlo_module(v4))
    return scopes


def scope_map_of(compiled):
    """-> {module name: {hlo_instruction_name: op_name}} read from an
    executable's own optimised HLO module(s) — compiled here or loaded
    from the executable cache — the map :func:`hlo_scope_map` reads
    from a trace's metadata plane, for traces taken without HLO protos
    (the device's op events carry instruction names only). Keyed by
    module, since instruction names repeat across modules. None when
    the executable exposes no HLO module.

    Walks the serialised HloModuleProto with the same decoder as the
    trace path: one pass over the module, seconds for the window
    program. Built only on request (core.jitcache.AotJit.scope_map),
    never by a run itself."""
    try:
        mods = compiled.runtime_executable().hlo_modules()
    except (AttributeError, RuntimeError):
        return None
    if not mods:
        return None
    return {m.name: _walk_hlo_module(
                memoryview(m.as_serialized_hlo_module_proto()))
            for m in mods}


# --- per-op SELF time ------------------------------------------------------

def _self_times(evs):
    """{metadata_id: self_ps} from nested (offset, duration) events on
    one line. Events nest strictly (a while-loop span contains its
    body's spans); sorting by (offset, -duration) makes each parent
    precede its children, and a close-upto stack walk charges every
    span only its own time minus its DIRECT children."""
    out = collections.Counter()
    stack = []                 # [end_ps, dur_ps, child_ps, mid]
    evs = sorted(evs, key=lambda e: (e[0], -e[1]))

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, dur, child, mid = stack.pop()
            out[mid] += max(dur - child, 0)
            if stack:
                stack[-1][2] += dur
    for off, dur, mid in evs:
        close(off)
        stack.append([off + dur, dur, 0, mid])
    close(float("inf"))
    return out


def device_self_times(path):
    """-> {hlo_name: total_self_ps} over every XLA op line in the
    file. On CPU the per-op events live on the ``/host:CPU`` plane's
    ``tf_XLATfrtCpuClient/*`` line; device backends put them on
    per-device planes' "XLA Ops" lines — both carry "XLA" in the line
    name, which is the filter."""
    buf = memoryview(open(path, "rb").read())
    out = collections.Counter()
    for fn, wt, v in _fields(buf):
        if fn != 1 or wt != 2:
            continue
        pname, emeta, lines = _plane_raw(v)
        if pname == "/host:metadata":
            continue
        names = {k: _meta_name(m) for k, m in emeta.items()}
        for lbuf in lines:
            lname, evs = _line_events(lbuf)
            if "XLA" not in lname:
                continue
            for mid, ps in _self_times(evs).items():
                out[names.get(mid, f"#{mid}")] += ps
    return out


def decode_dir(trace_dir):
    """-> (scopes, self_times) merged over every .xplane.pb under
    trace_dir (one file per profiled host)."""
    scopes = {}
    selfs = collections.Counter()
    paths = sorted(glob.glob(os.path.join(trace_dir, "**",
                                          "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    for p in paths:
        scopes.update(hlo_scope_map(p))
        for k, ps in device_self_times(p).items():
            selfs[k] += ps
    return scopes, selfs


# --- attribution -----------------------------------------------------------

def attribute(selfs, scopes):
    """Map per-op device self-times to pass labels.

    The INNERMOST label on the scope path wins: an op inside
    ``.../drain/w512/nic.rx_admit/tcp.rx/...`` belongs to ``tcp.rx``,
    not to the drain loop that contains it (outer scopes wrap the
    whole while-loop). Rung sublabels (w512/k32/dense) are recorded
    independently in ``rungs`` — the per-rung view keeps handler time,
    so it answers "what does rung X cost" for compaction decisions.

    Runtime scaffolding lines (``ThunkExecutor::Execute (wait for
    completion)`` and friends — thread-pool dispatch and idle waits,
    dominant on small CPU hosts) are NOT compute: they go to a
    separate ``runtime_ms`` bucket excluded from the attribution
    denominator. HLO instruction names never contain ``::`` or
    spaces, which is the filter.

    -> {"phases": {label: {"ms", "frac"}}, "rungs": {...},
        "total_ms", "attributed_ms", "attributed_frac", "ok",
        "runtime_ms", "residual_ms", "residual_frac",
        "residual_label", "residual_top": [{"op", "ms"}]}
    """
    phases = collections.Counter()
    rungs = collections.Counter()
    resid = collections.Counter()
    runtime_ps = 0
    for hlo, ps in selfs.items():
        if "::" in hlo or " " in hlo:
            runtime_ps += ps
            continue
        op = scopes.get(hlo)
        label = rung = None
        if op:
            for part in reversed(_scopes_of(op)):
                if rung is None and _RUNG_RE.match(part):
                    rung = part
                elif label is None and part in PASS_LABELS:
                    label = part
                if label is not None and rung is not None:
                    break
        if rung is not None:
            rungs[rung] += ps
            if label is None:
                label = "drain"       # rung scopes live inside drain
        if label is not None:
            phases[label] += ps
        else:
            resid[hlo] += ps
    total = sum(selfs.values()) - runtime_ps
    attributed = sum(phases.values())
    resid_ps = total - attributed

    def _tbl(ctr):
        return {k: {"ms": round(v / 1e9, 3),
                    "frac": round(v / total, 4) if total else 0.0}
                for k, v in sorted(ctr.items(), key=lambda kv: -kv[1])}
    frac = attributed / total if total else 0.0
    return {
        "phases": _tbl(phases),
        "rungs": _tbl(rungs),
        "total_ms": round(total / 1e9, 3),
        "attributed_ms": round(attributed / 1e9, 3),
        "attributed_frac": round(frac, 4),
        "ok": frac >= MIN_ATTRIBUTED,
        "runtime_ms": round(runtime_ps / 1e9, 3),
        "residual_ms": round(resid_ps / 1e9, 3),
        "residual_frac": round(1.0 - frac, 4) if total else 0.0,
        "residual_label": RESIDUAL,
        "residual_top": [{"op": k, "ms": round(v / 1e9, 3)}
                         for k, v in sorted(resid.items(),
                                            key=lambda kv: -kv[1])[:8]],
    }


def top_pass(dev):
    """-> (label, frac) of the largest attributed pass, or (None, 0)."""
    ph = (dev or {}).get("phases") or {}
    if not ph:
        return None, 0.0
    lbl = max(ph, key=lambda k: ph[k]["ms"])
    return lbl, ph[lbl]["frac"]


# --- lockstep occupancy ----------------------------------------------------

def occupancy(pass_mix, events, batch):
    """Lockstep efficiency from the drain's own pass accounting.

    pass_mix: {label: (width, n_passes)} — SimReport.cost["pass_mix"]
    (engine.window.pass_labels order: w-rungs, k-rungs, dense).
    events: executed events over the same span (chained NIC-TX
    included, so utilization is clamped at 1.0).
    batch: {label: event slots per lane per pass} —
    engine.window.pass_batches, the batch the drain itself runs on
    each rung (a label it does not name runs one, as dense does).

    A w-rung's counted passes run over its gathered width; inner
    sub-compaction (a k-rung pass inside a w-window) is not counted
    separately, so w-rung lane_steps is a conservative upper bound.

    -> {"lane_steps", "events", "passes", "utilization", "waste_frac",
        "per_rung": {label: {"width", "passes", "batch",
                             "lane_steps", "min_fill"}}}
    """
    per_rung = {}
    lane_steps = 0
    passes = 0
    # selection lower bounds: rung k_i is chosen when the active count
    # lands in (k_{i-1}, k_i], so its fill is at least (k_{i-1}+1)/k_i
    ws = sorted((int(lbl[1:]), lbl) for lbl in pass_mix
                if lbl.startswith("w") and lbl[1:].isdigit())
    ks = sorted((int(lbl[1:]), lbl) for lbl in pass_mix
                if lbl.startswith("k") and lbl[1:].isdigit())

    def _min_fill(lbl, width):
        for sizes in (ws, ks):
            order = [s for s, _ in sizes]
            for j, (s, l) in enumerate(sizes):
                if l == lbl:
                    prev = order[j - 1] if j else 0
                    return (prev + 1) / width if width else 0.0
        if lbl == "dense":
            prev = max([s for s, _ in ws + ks], default=0)
            return (prev + 1) / width if width else 0.0
        return 0.0
    for lbl, (width, n) in pass_mix.items():
        width, n = int(width), int(n)
        b = max(1, int(batch.get(lbl, 1)))
        steps = n * width * b
        lane_steps += steps
        passes += n
        per_rung[lbl] = {
            "width": width, "passes": n, "batch": b,
            "lane_steps": steps,
            "min_fill": round(_min_fill(lbl, width), 4),
        }
    util = min(1.0, events / lane_steps) if lane_steps else 0.0
    return {
        "lane_steps": int(lane_steps),
        "events": int(events),
        "passes": int(passes),
        "utilization": round(util, 4),
        "waste_frac": round(1.0 - util, 4),
        "per_rung": per_rung,
    }


def shard_occupancy(shard_pass_acc, shard_events, labels_sizes, batch):
    """Per-shard waste view, composing with the PR 6 shard.imbalance
    gauges: the same occupancy math per shard row.

    shard_pass_acc: [n_shards][n_rungs] pass counts;
    shard_events: [n_shards] executed events;
    labels_sizes: [(label, width)] in pass-index order;
    batch: {label: batch} as occupancy takes it.

    -> {"per_shard": [waste_frac...], "utilization": [...],
        "skew": max/mean of per-shard utilization (1.0 = balanced)}
    """
    wastes, utils = [], []
    for row, ev in zip(shard_pass_acc, shard_events):
        mix = {lbl: (size, int(n))
               for (lbl, size), n in zip(labels_sizes, row)}
        o = occupancy(mix, int(ev), batch)
        wastes.append(o["waste_frac"])
        utils.append(o["utilization"])
    mean = sum(utils) / len(utils) if utils else 0.0
    skew = (max(utils) / mean) if mean else 0.0
    return {"per_shard": wastes, "utilization": utils,
            "skew": round(skew, 4)}


# --- capture ---------------------------------------------------------------

class Capture:
    """jax.profiler trace around the first N window chunks of a run.

    The trace arms at the first chunk_done() — i.e. AFTER the first
    chunk, which holds the XLA compilation. Tracing a compile is
    ruinously slow on small hosts and its events would pollute the
    pass table anyway; the HLO metadata plane is emitted at execution
    time, so a post-compile trace still decodes fully. The next
    ``max_chunks`` chunks are traced, then the profiler stops while
    the run continues untraced.

    Profiling is observation only — the compiled program, its inputs
    and the digest chain are untouched (tests/test_passcope.py pins
    passcope-on chains byte-identical to plain runs). A CPU backend that
    refuses the profiler degrades to ``available: False`` with the
    error recorded; on a TPU a capture that was asked for and failed
    is an error.
    """

    def __init__(self, trace_dir, max_chunks=None):
        self.trace_dir = trace_dir
        self.max_chunks = max_chunks or int(os.environ.get(
            "SHADOW_TPU_PASSCOPE_CHUNKS", str(DEFAULT_TRACE_CHUNKS)))
        self.active = False
        self.stopped = False
        self.error = None
        self.chunks = 0

    def start(self):
        if self.active or self.stopped:
            return
        import jax
        try:
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
            self.active = True
        except Exception as e:
            if jax.default_backend() == "tpu":
                raise      # a capture asked for on the chip must work
            self.error = repr(e)     # refusing CPU backend -> degrade
            self.stopped = True

    def chunk_done(self):
        if self.stopped:
            return
        if not self.active:
            # first chunk boundary: compilation is behind us — arm
            self.start()
            return
        self.chunks += 1
        if self.chunks >= self.max_chunks:
            self.stop()

    def stop(self):
        if self.active:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:
                self.error = self.error or repr(e)
            self.active = False
        self.stopped = True

    def result(self):
        """-> the device_phases dict (attribute() output +
        available/trace_dir/chunks_traced), or available: False."""
        self.stop()
        base = {"trace_dir": self.trace_dir,
                "chunks_traced": self.chunks}
        import jax
        on_tpu = jax.default_backend() == "tpu"
        if self.error:
            if on_tpu:
                raise RuntimeError(f"passcope: capture failed on the "
                                   f"chip: {self.error}")
            return {"available": False, "error": self.error, **base}
        try:
            scopes, selfs = decode_dir(self.trace_dir)
        except Exception as e:
            if on_tpu:
                raise
            return {"available": False, "error": repr(e), **base}
        if not selfs:
            if on_tpu:
                raise RuntimeError("passcope: no XLA device events in "
                                   f"the chip trace {self.trace_dir}")
            return {"available": False,
                    "error": "no XLA device events in trace", **base}
        out = attribute(selfs, scopes)
        out["available"] = True
        out.update(base)
        return out


# --- publishing ------------------------------------------------------------

def publish(registry, occ=None, dev=None, shards=None):
    """passcope.* / occupancy.* gauges — the sections
    obs.metrics.Registry.snapshot() assembles into metrics.json."""
    if occ:
        registry.gauge("occupancy.waste_frac").set(occ["waste_frac"])
        registry.gauge("occupancy.utilization").set(occ["utilization"])
        registry.gauge("occupancy.lane_steps").set(occ["lane_steps"])
        registry.gauge("occupancy.passes").set(occ["passes"])
        for lbl, r in occ["per_rung"].items():
            registry.gauge(
                f"occupancy.rung_passes.{lbl}").set(r["passes"])
            registry.gauge(
                f"occupancy.rung_lane_steps.{lbl}").set(r["lane_steps"])
    if shards:
        registry.gauge("occupancy.shard_skew").set(shards["skew"])
        for i, w in enumerate(shards["per_shard"]):
            registry.gauge(f"occupancy.shard_waste.{i}").set(w)
    if dev and dev.get("available"):
        registry.gauge("passcope.total_ms").set(dev["total_ms"])
        registry.gauge("passcope.attributed_frac").set(
            dev["attributed_frac"])
        registry.gauge("passcope.residual_ms").set(dev["residual_ms"])
        for lbl, ph in dev["phases"].items():
            registry.gauge(f"passcope.phase_ms.{lbl}").set(ph["ms"])


def format_report(dev=None, occ=None):
    """Human-readable pass table + occupancy block (the --passcope
    CLI print and tools/trace_report.py's device section)."""
    lines = []
    if dev is not None:
        if not dev.get("available"):
            lines.append("passcope: device trace unavailable — "
                         f"{dev.get('error')}")
        else:
            lines.append(f"passcope: device pass table "
                         f"({dev['total_ms']:.1f} ms device time, "
                         f"{dev['chunks_traced']} chunks traced)")
            lines.append(f"  {'pass':<18} {'ms':>10} {'share':>7}")
            for lbl, ph in dev["phases"].items():
                lines.append(f"  {lbl:<18} {ph['ms']:>10.2f} "
                             f"{100 * ph['frac']:>6.1f}%")
            lines.append(f"  {dev['residual_label']:<18} "
                         f"{dev['residual_ms']:>10.2f} "
                         f"{100 * dev['residual_frac']:>6.1f}%")
            if dev["rungs"]:
                rung = ", ".join(f"{k}={v['ms']:.1f}ms"
                                 for k, v in dev["rungs"].items())
                lines.append(f"  drain rungs: {rung}")
            if dev.get("runtime_ms"):
                lines.append(f"  (runtime scaffolding excluded: "
                             f"{dev['runtime_ms']:.1f} ms)")
            if not dev["ok"]:
                lines.append(
                    f"  WARNING: only "
                    f"{100 * dev['attributed_frac']:.1f}% attributed "
                    f"(floor {100 * MIN_ATTRIBUTED:.0f}%) — top "
                    "residual ops: " + ", ".join(
                        r["op"] for r in dev["residual_top"][:3]))
    if occ:
        lines.append(
            f"occupancy: waste_frac={occ['waste_frac']:.3f} "
            f"(events={occ['events']} over {occ['lane_steps']} "
            f"lane-steps, {occ['passes']} passes)")
        for lbl, r in occ["per_rung"].items():
            if r["passes"]:
                lines.append(
                    f"  rung {lbl:<8} passes={r['passes']:<8} "
                    f"width={r['width']:<7} batch={r['batch']} "
                    f"min_fill={r['min_fill']:.3f}")
    return "\n".join(lines)


# --- self-check ------------------------------------------------------------

def fixture_path():
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        "tests", "data", "passcope_fixture.xplane.pb")


def self_check(path=None):
    """Decode the committed fixture xplane (hand-built varint records,
    tests/helpers/xplane_encode.py) and assert the pass-table schema —
    the CI simlint-job smoke (no jax; run via
    ``python tools/xplane_profile.py --self-check``)."""
    path = path or fixture_path()
    scopes = hlo_scope_map(path)
    selfs = device_self_times(path)
    assert scopes and selfs, f"fixture decoded empty: {path}"
    dev = attribute(selfs, scopes)
    assert set(dev["phases"]) <= set(PASS_LABELS), dev["phases"]
    assert all(_RUNG_RE.match(k) for k in dev["rungs"]), dev["rungs"]
    assert dev["ok"] and dev["attributed_frac"] >= MIN_ATTRIBUTED, dev
    assert dev["residual_label"] == RESIDUAL
    assert abs(sum(p["frac"] for p in dev["phases"].values())
               + dev["residual_frac"] - 1.0) < 0.01, dev
    # the expected fixture content, exactly (self-time math included:
    # the thunk parent's glue is runtime scaffolding, not
    # double-counted; copy.5 is the unscoped-HLO residual)
    assert dev["phases"]["drain"]["ms"] == 40.0, dev
    assert dev["phases"]["exchange"]["ms"] == 30.0, dev
    assert dev["phases"]["tcp.rx"]["ms"] == 20.0, dev
    assert dev["phases"]["advance"]["ms"] == 5.0, dev
    assert dev["residual_ms"] == 3.0, dev
    assert dev["runtime_ms"] == 2.0, dev
    assert dev["total_ms"] == 98.0, dev
    assert dev["attributed_frac"] == round(95 / 98, 4), dev
    assert dev["residual_top"][0]["op"] == "copy.5", dev
    assert dev["rungs"]["w512"]["ms"] == 90.0, dev
    # occupancy arithmetic, exactly
    occ = occupancy({"k32": (32, 10), "dense": (64, 2)},
                    events=200, batch={"k32": 4, "dense": 1})
    assert occ["lane_steps"] == 10 * 32 * 4 + 2 * 64 * 1, occ
    assert occ["passes"] == 12, occ
    assert occ["utilization"] == round(200 / 1408, 4), occ
    assert occ["waste_frac"] == round(1 - 200 / 1408, 4), occ
    assert occ["per_rung"]["k32"]["min_fill"] == round(1 / 32, 4), occ
    assert occ["per_rung"]["dense"]["min_fill"] == round(33 / 64, 4), occ
    sh = shard_occupancy([[10, 2], [2, 0]], [200, 40],
                         [("k32", 32), ("dense", 64)], {"k32": 4})
    assert len(sh["per_shard"]) == 2 and sh["skew"] >= 1.0, sh
    print("passcope: self-check OK (decoder + attribution + occupancy)")
    return 0


def load_json(path):
    """Read a device_phases JSON a --passcope run wrote into its run
    dir (tools/trace_report.py merges it under the host phase table)."""
    with open(path) as f:
        return json.load(f)
