"""Per-phase wall-cost attribution: every wall-millisecond named.

The reference tracks per-component cost continuously (tracker
heartbeats, shd-tracker.c:266; scheduler barrier self-times,
shd-scheduler.c:250-252) but never answers "what fraction of this
run's wall went to which engine phase". Here the trace recorder
(obs.trace) already spans every phase the host-side loop executes —
setup, the cold XLA compile, each compiled window chunk, hosted-app
steps, pcap drains, tracker heartbeats, checkpoint saves, digest
records, fault applications, report finalization — so attribution is
pure span arithmetic: per-span SELF-time (total minus directly nested
children, the same stack walk tools/trace_report.py uses), mapped
through :data:`PHASE_OF` into a small set of named phases, compared
against the run's measured wall.

The contract the perf tooling builds on (tools/perf_report.py,
docs/performance.md): phases must sum to >= :data:`MIN_ATTRIBUTED`
of the measured wall or the report labels the residual explicitly —
"93% attributed, 7% unattributed (host loop glue)" is an answer;
a silent gap is not.

Everything here is host-side and read-only: attribution never touches
device state, so a ``--perf`` run's digest chain is byte-identical to
a plain run's (asserted by tests/test_perf.py).
"""

from __future__ import annotations

from collections import defaultdict

# span name -> phase name. Spans not listed attribute under their own
# name (visible, never silently dropped); the residual bucket below is
# only for wall time NO span covered.
PHASE_OF = {
    "run.setup": "setup",            # topology/mesh placement, writers
    "sim.build": "setup",            # Simulation(...)
    "compile+first_chunk": "compile",  # cold XLA build (+ 1st chunk)
    "chunk": "window",               # compiled drain+exchange chunks
    "chunk.dispatch": "window",      # the step call until it returns
    "chunk.wait": "window",          # until its results are on the host
    "hosting.step": "hosting",       # hosted-app CPU tier per window
    "pcap.drain": "pcap",
    "tracker.heartbeat": "tracker",
    "checkpoint.save": "checkpoint",
    "digest.record": "digest",
    "digest.pull": "digest",         # device -> host state transfer
    "digest.hash": "digest",         # canonicalise, hash, chain
    "faults.apply": "faults",
    "report.finalize": "finalize",
    "build": "setup",
    # AOT executable cache (serving.aotcache, PR 13): the real XLA
    # build vs a persistent-cache load. Both nest inside the first
    # chunk's chunk.dispatch, inside compile+first_chunk, whose SELF
    # time (glue between its children) stays under "compile"; the
    # first chunk's dispatch and wait are window work like any
    # chunk's — so a phase map states mechanically whether "cold"
    # paid a compile (compile-miss > 0) or opened warm from disk
    # (compile-hit only).
    # tools/perf_regress.py's compile-bound exemption reads
    # compile-miss when present: a cache-hit run is gateable.
    "jitcache.compile": "compile-miss",
    "jitcache.load": "compile-hit",
    # memory observatory (obs.memscope, PR 15): the per-executable
    # XLA cost/memory-analysis capture that AotJit runs right after
    # materializing a program. Kept OUT of compile-miss on purpose —
    # analysis wall is observatory overhead, not the XLA build.
    "memscope.analyze": "memscope",
}

RESIDUAL = "unattributed (host loop glue)"

# the attribution-quality floor: below this the report flags itself
MIN_ATTRIBUTED = 0.90


def self_times(events) -> dict:
    """Per span name: [count, total_us, self_us]. Self-time excludes
    directly nested child spans per (pid, tid) track — the standard
    sort-and-stack walk (an enclosing span sorts before the spans it
    contains via (ts, -dur))."""
    agg = {}
    tracks = defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        tracks[(e.get("pid", 0), e.get("tid", 0))].append(e)
    for evs in tracks.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []  # [end_ts, child_sum_us, name, dur_us]

        def close(upto):
            while stack and stack[-1][0] <= upto + 1e-9:
                end, child, name, dur = stack.pop()
                a = agg.setdefault(name, [0, 0.0, 0.0])
                a[0] += 1
                a[1] += dur
                a[2] += max(dur - child, 0.0)
                if stack:
                    stack[-1][1] += dur

        for e in evs:
            close(e["ts"])
            stack.append([e["ts"] + e["dur"], 0.0, e["name"], e["dur"]])
        close(float("inf"))
    return agg


def attribute(events, wall_s: float, n_events: int = None) -> dict:
    """Attribute `wall_s` seconds of run wall to named phases from the
    trace `events` (Chrome trace-event dicts, obs.trace format).

    Returns::

        {"wall_s": ..., "events": ...,
         "phases": {phase: {"wall_s", "frac", "count",
                            "us_per_event"?}},   # sorted by wall desc
         "attributed_s": ..., "attributed_frac": ...,
         "residual_s": ..., "residual_frac": ...,
         "residual_label": RESIDUAL,
         "ok": attributed_frac >= MIN_ATTRIBUTED}

    `n_events` (simulated events executed) adds a per-event cost to
    each phase — "what does one simulated event pay this phase".
    """
    agg = self_times(events)
    walls = defaultdict(float)
    counts = defaultdict(int)
    for name, (c, total, self_us) in agg.items():
        phase = PHASE_OF.get(name, name)
        walls[phase] += self_us / 1e6
        counts[phase] += c
    attributed = sum(walls.values())
    # spans can slightly overlap the measured wall (perf_counter noise,
    # spans opened before wall0); clamp so fractions stay sane
    residual = max(wall_s - attributed, 0.0)
    phases = {}
    for phase in sorted(walls, key=lambda p: -walls[p]):
        row = {"wall_s": round(walls[phase], 6),
               "frac": round(walls[phase] / wall_s, 4) if wall_s else 0.0,
               "count": counts[phase]}
        if n_events:
            row["us_per_event"] = round(walls[phase] * 1e6 / n_events, 3)
        phases[phase] = row
    frac = min(attributed / wall_s, 1.0) if wall_s else 0.0
    out = {
        "wall_s": round(wall_s, 6),
        "phases": phases,
        "attributed_s": round(min(attributed, wall_s), 6),
        "attributed_frac": round(frac, 4),
        "residual_s": round(residual, 6),
        "residual_frac": round(residual / wall_s, 4) if wall_s else 0.0,
        "residual_label": RESIDUAL,
        "ok": frac >= MIN_ATTRIBUTED,
    }
    if n_events is not None:
        out["events"] = int(n_events)
    return out


def publish(attribution: dict, registry) -> None:
    """Expose an attribution as ``perf.*`` gauges (obs.metrics): one
    ``perf.phase.<name>_s`` per phase plus the attributed fraction —
    so metrics.json carries the same breakdown the report prints."""
    for phase, row in attribution["phases"].items():
        key = phase.split(" ")[0]  # gauge-safe
        registry.gauge(f"perf.phase.{key}_s").set(row["wall_s"])
    registry.gauge("perf.attributed_frac").set(
        attribution["attributed_frac"])
    registry.gauge("perf.residual_s").set(attribution["residual_s"])


def format_report(attribution: dict) -> str:
    """Human-readable phase table (the --perf CLI output)."""
    lines = [f"== perf: phase attribution "
             f"({attribution['attributed_frac'] * 100:.1f}% of "
             f"{attribution['wall_s']:.3f}s wall attributed) =="]
    lines.append(f"{'phase':<12} {'wall_s':>10} {'frac':>7} "
                 f"{'count':>7} {'us/event':>10}")
    for phase, row in attribution["phases"].items():
        upe = row.get("us_per_event")
        lines.append(
            f"{phase:<12} {row['wall_s']:>10.3f} "
            f"{row['frac'] * 100:>6.1f}% {row['count']:>7} "
            f"{upe if upe is not None else '-':>10}")
    lines.append(
        f"{'residual':<12} {attribution['residual_s']:>10.3f} "
        f"{attribution['residual_frac'] * 100:>6.1f}%    "
        f"<- {attribution['residual_label']}")
    if not attribution["ok"]:
        lines.append(
            f"WARNING: only {attribution['attributed_frac'] * 100:.1f}% "
            f"of the wall is attributed (floor "
            f"{MIN_ATTRIBUTED * 100:.0f}%) — the unattributed "
            "remainder is host-side time between spans")
    return "\n".join(lines)
