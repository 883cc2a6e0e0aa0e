"""memscope: the device-memory & XLA-cost observatory.

The reference tracker reports per-host RAM and process RSS every
heartbeat (shd-tracker.c:539-546, shd-slave.c:374-395); this repo
attributed >=90% of *wall time* in PR 6 but memory stayed a blind
spot: the roofline was a hand model with a duplicated 819 GB/s peak,
no XLA ``cost_analysis``/``memory_analysis`` was ever captured, and
nothing could say where the bytes go per field or per pass — while
ROADMAP item 2's 100k->1M-host push names memory-layout refactors as
the blocker. This module is the measure-then-gate counterpart for
bytes (docs/observability.md "Memory observatory"):

- **Static byte census** (:func:`state_census`): per-field
  ``dtype x shape`` bytes of the ``Hosts``/``HostParams``/``Shared``
  pytrees at the run's actual H, rolled up by
  ``engine.state.STATE_SECTIONS`` and split hot/cold per the PR-12
  ``HOT_FIELDS``/``COLD_WHEN`` declaration — the hot-split's HBM
  saving as a number, not a claim. A pure-stdlib dims table
  (:data:`HOSTS_DIMS`/:data:`HP_DIMS`) backs the jax-free consumers
  (``tools/state_matrix.py``'s bytes column); it is pinned exactly
  against ``engine.state.shape_census`` (eval_shape over the real
  ``alloc_hosts``) by tests/test_memscope.py, so the two definitions
  cannot drift.
- **Compiled-program capture** (:func:`observe_executable`, hooked in
  ``core.jitcache.AotJit``): XLA ``cost_analysis()`` (flops, bytes
  accessed) and ``memory_analysis()`` (argument/output/temp/generated
  -code bytes) per compiled entry, kept in :data:`CAPTURED`,
  published as ``cost.*``/``mem.*`` gauges and a ``memscope.analyze``
  span. Backends that refuse either analysis degrade gracefully
  (``available: False`` with the error recorded), never an exception.
- **Live watermarks** (:class:`Watermark`): per-chunk device-buffer
  high-water sampling — real device memory stats where the backend
  provides them (per device, so a mesh run reports per-shard peaks),
  ``resource``/RSS fallback on CPU — wired into the tracker heartbeat
  (``dev=`` column) and the perf ledger (``mem_peak_bytes``, gated by
  ``tools/perf_regress.py``'s memory gate).
- **One HBM-peak definition** (:func:`hbm_peak_gbps`): the roofline
  peak from one table keyed by ``device_kind``; an unlisted device has
  none, and its roofline fractions read "not measured".

Everything here is host-side and read-only: census, capture and
watermark sampling never touch device state, so a memscope-enabled
run's digest chain is byte-identical to a plain run's (asserted by
tests/test_memscope.py). The module imports nothing heavier than the
stdlib at import time — jax and the engine load lazily inside the
functions that need them — so headless tools (``tools/state_matrix``,
``tools/capacity_plan --help``) can load it by file path.
"""

from __future__ import annotations

import os
import sys

# --- the HBM peak: one table keyed by device_kind -------------------------

# Published HBM bandwidth of one chip, GB/s, keyed by jax's
# ``device_kind``. TPU v5e: 819 GB/s (Google Cloud "TPU v5e"
# documentation). A device not in the table has no peak, and its runs
# carry no roofline fraction ("not measured") — never a guessed one.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def hbm_peak_gbps(device_kind: str):
    """The chip HBM peak the roofline fractions divide by, or None
    for a device the table does not list — the ONE definition behind
    SimReport.cost_model and the run loop's cost bookkeeping."""
    return HBM_PEAK_GBPS.get(device_kind)


# --- the stdlib shape table ------------------------------------------------
#
# Per-host trailing dims + dtype of every Hosts/HostParams column, as
# LITERALS: the jax-free consumers (state_matrix's bytes column, the
# capacity planner's headless mode) read these without importing the
# engine. Symbolic dims resolve through dims_of(); the table is pinned
# EXACTLY against engine.state.shape_census (eval_shape over the real
# alloc_hosts) by tests/test_memscope.py::test_census_exactness — an
# alloc_hosts edit that forgets this table fails that test by field
# name.

DTYPE_BYTES = {"i64": 8, "i32": 4, "u32": 4, "f32": 4, "bool": 1,
               "i16": 2, "u16": 2, "i8": 1}
# canonical numpy names, for pinning against real array dtypes
DTYPE_NAMES = {"i64": "int64", "i32": "int32", "u32": "uint32",
               "f32": "float32", "bool": "bool",
               "i16": "int16", "u16": "uint16", "i8": "int8"}

# constant dims mirrored from their owning modules (pinned by the
# exactness test): net.packet.PKT_WORDS, net.sack.K,
# engine.defs.N_STATS, obs.netscope.NS_KINDS/NS_BUCKETS
PKT_WORDS = 13
SACK_K = 4
N_STATS = 24
NS_KINDS = 4
NS_BUCKETS = 32

HOSTS_DIMS = (
    ("eq_time", ("Q",), "i64"),
    ("eq_seq", ("Q",), "i32"),
    ("eq_kind", ("Q",), "i32"),
    ("eq_pkt", ("Q", "PKT"), "i32"),
    ("eq_ctr", (), "i32"),
    ("eq_next", (), "i64"),
    ("rng_ctr", (), "i32"),
    ("cpu_avail", (), "i64"),
    ("nic_busy", (), "i64"),
    ("nic_sched", (), "bool"),
    ("nic_rr", (), "i32"),
    ("nic_rx_until", (), "i64"),
    ("txq_pkt", ("T", "PKT"), "i32"),
    ("txq_head", (), "i32"),
    ("txq_cnt", (), "i32"),
    ("pkt_ctr", (), "i32"),
    ("next_eport", (), "i32"),
    ("sk_used", ("S",), "bool"),
    ("sk_proto", ("S",), "i32"),
    ("sk_state", ("S",), "i32"),
    ("sk_lport", ("S",), "i32"),
    ("sk_rport", ("S",), "i32"),
    ("sk_rhost", ("S",), "i32"),
    ("sk_parent", ("S",), "i32"),
    ("sk_snd_una", ("S",), "i64"),
    ("sk_snd_nxt", ("S",), "i64"),
    ("sk_snd_max", ("S",), "i64"),
    ("sk_snd_end", ("S",), "i64"),
    ("sk_rcv_nxt", ("S",), "i64"),
    ("sk_ooo_s", ("S", "K"), "i64"),
    ("sk_ooo_e", ("S", "K"), "i64"),
    ("sk_sack_s", ("S", "K"), "i64"),
    ("sk_sack_e", ("S", "K"), "i64"),
    ("sk_hole_end", ("S",), "i64"),
    ("sk_rex_nxt", ("S",), "i64"),
    ("sk_peer_fin", ("S",), "i64"),
    ("sk_fin_acked", ("S",), "bool"),
    ("sk_close_after", ("S",), "bool"),
    ("sk_cwnd", ("S",), "f32"),
    ("sk_ssthresh", ("S",), "f32"),
    ("sk_srtt", ("S",), "i64"),
    ("sk_rtt_min", ("S",), "i64"),
    ("sk_rttvar", ("S",), "i64"),
    ("sk_rto", ("S",), "i64"),
    ("sk_rto_deadline", ("S",), "i64"),
    ("sk_timer_on", ("S",), "bool"),
    ("sk_timer_gen", ("S",), "i32"),
    ("sk_dupacks", ("S",), "i32"),
    ("sk_rtt_seq", ("S",), "i64"),
    ("sk_rtt_time", ("S",), "i64"),
    ("sk_ctl", ("S",), "i32"),
    ("sk_peer_rwnd", ("S",), "i64"),
    ("sk_sndbuf", ("S",), "i64"),
    ("sk_rcvbuf", ("S",), "i64"),
    ("sk_hs_time", ("S",), "i64"),
    ("sk_last_tx", ("S",), "i64"),
    ("sk_syn_tag", ("S",), "i32"),
    ("sk_proc", ("S",), "i32"),
    ("sk_app_ref", ("S",), "i32"),
    ("sk_cc_wmax", ("S",), "f32"),
    ("sk_cc_epoch", ("S",), "i64"),
    ("sk_cc_k", ("S",), "f32"),
    ("app_node", ("PP",), "i32"),
    ("app_r", ("PP", 8), "i64"),
    ("app_proc", (), "i32"),
    ("tgen_sync", ("SY",), "i32"),
    ("ob_pkt", ("O", "PKT"), "i32"),
    ("ob_time", ("O",), "i64"),
    ("ob_cnt", (), "i32"),
    ("ob_next", (), "i64"),
    ("hw_time", ("HW",), "i64"),
    ("hw_pkt", ("HW", "PKT"), "i32"),
    ("hw_cnt", (), "i32"),
    ("hw_drop", (), "i32"),
    ("tr_time", ("TC",), "i64"),
    ("tr_pkt", ("TC", "PKT"), "i32"),
    ("tr_dir", ("TC",), "i32"),
    ("tr_cnt", (), "i32"),
    ("tr_drop", (), "i32"),
    ("stats", ("NST",), "i64"),
    ("ns_hist", ("NSK", "NSB"), "i64"),
    ("cap_peaks", (4,), "i32"),
)

# the Shared fields that scale with H (replicated per-host tables —
# engine.state.Shared declares exactly these as [H] rows; everything
# else there is topology-sized or scalar, i.e. fixed cost for the
# capacity model). Pinned against the live tree by
# tests/test_memscope.py.
SHARED_PER_HOST_FIELDS = ("host_vertex", "host_bw_up", "host_bw_down")

HP_DIMS = (
    ("hid", (), "i32"),
    ("rng_stream", (), "u32"),
    ("vertex", (), "i32"),
    ("bw_up", (), "i64"),
    ("bw_down", (), "i64"),
    ("app_kind", ("PP",), "i32"),
    ("app_cfg", ("PP", 8), "i64"),
    ("nic_buf", (), "i64"),
    ("cpu_cost", (), "i64"),
    ("cpu_threshold", (), "i64"),
    ("rcvbuf0", (), "i64"),
    ("sndbuf0", (), "i64"),
    ("pcap_on", (), "bool"),
)


# The shrink campaign's at-rest dtype overlay (docs/performance.md):
# when an EngineConfig allocates the narrow layout (wide_state == 0,
# the default), these Hosts columns live at a narrower dtype than the
# canonical wide one HOSTS_DIMS declares. A LITERAL mirror of
# engine.state.NARROW_SPEC's (field -> narrow dtype) projection,
# pinned against it by tests/test_shrink.py and against live arrays
# by the census exactness pin — a NARROW_SPEC edit that forgets this
# table fails by field name. HOSTS_DIMS itself stays wide-canonical:
# it documents the COMPUTE dtype handlers see, and the digest's
# canonical form.
NARROW_DTYPES = {
    "sk_proto": "i8", "sk_state": "i8", "sk_ctl": "i8",
    "sk_lport": "u16", "sk_rport": "u16",
    "sk_snd_una": "i32", "sk_snd_nxt": "i32", "sk_snd_max": "i32",
    "sk_snd_end": "i32", "sk_rcv_nxt": "i32",
    "sk_ooo_s": "i32", "sk_ooo_e": "i32",
    "sk_sack_s": "i32", "sk_sack_e": "i32",
    "sk_hole_end": "i32", "sk_rex_nxt": "i32", "sk_peer_fin": "i32",
    "sk_rtt_seq": "i32",
    "sk_peer_rwnd": "i32", "sk_sndbuf": "i32", "sk_rcvbuf": "i32",
}


def effective_dtype(field: str, dt: str, cfg=None) -> str:
    """The AT-REST dtype of a Hosts column under this config: the
    NARROW_DTYPES overlay applies unless cfg asks for the wide layout
    (wide_state truthy). None = EngineConfig defaults = narrow."""
    wide = int(getattr(cfg, "wide_state", 0)) if cfg is not None else 0
    return dt if wide else NARROW_DTYPES.get(field, dt)


def dims_of(cfg=None) -> dict:
    """Symbolic-dim sizes from an EngineConfig (duck-typed: anything
    with the cap attributes works, so headless callers can pass a
    plain namespace). None = the EngineConfig defaults — the reference
    point state_matrix's bytes/host column uses."""
    def cap(name, default):
        return int(getattr(cfg, name, default)) if cfg is not None \
            else default

    return {
        "Q": cap("qcap", 32), "S": cap("scap", 16),
        "O": cap("obcap", 32), "T": cap("txqcap", 16),
        "PP": max(cap("procs_per_host", 1), 1),
        "SY": max(cap("synccap", 1), 1),
        "HW": max(cap("hostedcap", 1), 1),
        "TC": max(cap("tracecap", 0), 1),
        "K": SACK_K, "PKT": PKT_WORDS, "NST": N_STATS,
        # netscope's bucket axis is zero-capacity when the knob is off
        # (engine.state.alloc_hosts) — the census must agree
        "NSK": NS_KINDS,
        "NSB": NS_BUCKETS if cap("netscope", 0) else 0,
    }


def row_shape(dims_spec: tuple, dims: dict) -> tuple:
    """Concrete per-host trailing shape for a table row."""
    return tuple(d if isinstance(d, int) else dims[d]
                 for d in dims_spec)


def row_bytes(field: str, cfg=None, table=HOSTS_DIMS) -> int:
    """Per-host bytes of one column at this config (stdlib path).
    Hosts columns honor the at-rest NARROW_DTYPES overlay
    (effective_dtype); HP_DIMS rows have no narrow layout."""
    dims = dims_of(cfg)
    for name, dspec, dt in table:
        if name == field:
            if table is HOSTS_DIMS:
                dt = effective_dtype(name, dt, cfg)
            n = DTYPE_BYTES[dt]
            for d in row_shape(dspec, dims):
                n *= d
            return n
    raise KeyError(f"unknown field {field!r}")


def table_row_bytes(cfg=None, table=HOSTS_DIMS) -> dict:
    """{field: per-host bytes} for a whole dims table (stdlib path —
    what state_matrix's bytes/host column reads), at the layout this
    config actually allocates (NARROW_DTYPES overlay on Hosts)."""
    dims = dims_of(cfg)
    out = {}
    for name, dspec, dt in table:
        if table is HOSTS_DIMS:
            dt = effective_dtype(name, dt, cfg)
        n = DTYPE_BYTES[dt]
        for d in row_shape(dspec, dims):
            n *= d
        out[name] = n
    return out


# --- the census ------------------------------------------------------------

def _tree_field_bytes(tree) -> dict:
    """{field: (bytes, dtype, shape)} from a live chex dataclass of
    arrays (shape/dtype metadata only — no device sync, no transfer)."""
    out = {}
    for f in tree.__dataclass_fields__:
        a = getattr(tree, f)
        n = a.dtype.itemsize
        for d in a.shape:
            n *= int(d)
        out[f] = (n, str(a.dtype), tuple(int(d) for d in a.shape))
    return out


def state_census(cfg, hosts=None, hp=None, sh=None) -> dict:
    """The static byte census: per-field bytes at the run's actual H,
    rolled up by STATE_SECTIONS and split hot/cold per HOT_FIELDS and
    the config-gated hot_fields(cfg) runtime set.

    With only `cfg`, Hosts/HostParams shapes come from
    ``engine.state.shape_census`` (eval_shape — zero allocation) and
    the topology-sized Shared tree is omitted; passing the live trees
    (a built Simulation's hosts/hp/sh) censuses exactly what the run
    holds, Shared included. Either way this imports the engine (jax);
    headless callers use the stdlib table helpers above instead."""
    from ..engine.state import (COLD_FIELDS, HOT_FIELDS, hot_fields,
                                section_of, shape_census)

    H = cfg.num_hosts

    def _nbytes(shape, dtype_name):
        n = {"int64": 8, "int32": 4, "uint32": 4, "float32": 4,
             "bool": 1, "int16": 2, "uint16": 2, "int8": 1}[dtype_name]
        for d in shape:
            n *= int(d)
        return n

    if hosts is not None:
        hosts_fields = _tree_field_bytes(hosts)
    else:
        hosts_fields = {f: (_nbytes(shape, dt), dt, shape)
                        for f, (shape, dt) in shape_census(cfg).items()}
    runtime_hot = set(hot_fields(cfg))

    fields = {}
    sections = {}
    hot_b = cold_b = runtime_b = 0
    for f, (b, dt, shape) in hosts_fields.items():
        sec = section_of(f)
        fields[f] = {"bytes": b, "per_host": b // max(H, 1),
                     "dtype": dt, "shape": list(shape),
                     "section": sec,
                     "hot": f in HOT_FIELDS,
                     "hot_runtime": f in runtime_hot}
        sections[sec] = sections.get(sec, 0) + b
        if f in COLD_FIELDS:
            cold_b += b
        else:
            hot_b += b
        if f in runtime_hot:
            runtime_b += b
    total_h = hot_b + cold_b

    out = {
        "H": H,
        "hosts": {
            "fields": fields,
            "bytes": total_h,
            "per_host": total_h // max(H, 1),
            "sections": sections,
            "hot": {
                # static split (HOT_FIELDS vs COLD_FIELDS)
                "static_bytes": hot_b,
                "static_cold_bytes": cold_b,
                # the AS-CONFIGURED drain working set (COLD_WHEN gates
                # active): the bytes every rung gather/scatter and
                # loop carry actually moves — the split's saving is
                # bytes - runtime_bytes
                "runtime_bytes": runtime_b,
                "runtime_cold_bytes": total_h - runtime_b,
                "runtime_columns": len(runtime_hot),
            },
        },
    }

    if hp is not None:
        hpf = _tree_field_bytes(hp)
    else:
        hpf = {f: (row_bytes(f, cfg, HP_DIMS) * H,
                   DTYPE_NAMES[dt], None)
               for f, _, dt in HP_DIMS}
    hp_total = 0
    hp_fields = {}
    for f, (b, dt, shape) in hpf.items():
        hp_fields[f] = {"bytes": b, "per_host": b // max(H, 1),
                        "dtype": dt}
        hp_total += b
    out["hp"] = {"fields": hp_fields, "bytes": hp_total,
                 "per_host": hp_total // max(H, 1)}

    sh_per_host = sh_fixed = 0
    if sh is not None:
        shf = _tree_field_bytes(sh)
        sh_fields = {}
        for f, (b, dt, shape) in shf.items():
            # per-host replicated tables scale with H; the topology
            # oracle and scalars are fixed cost. Classified by NAME
            # (the declared contract, SHARED_PER_HOST_FIELDS) — a
            # shape[0] == H test would misfile the O(V^2) oracle as
            # linear whenever a topology happens to put one vertex
            # per host, corrupting every ladder extrapolation
            scales = f in SHARED_PER_HOST_FIELDS
            sh_fields[f] = {"bytes": b, "dtype": dt,
                            "scales_with_h": scales}
            if scales:
                sh_per_host += b // max(H, 1)
            else:
                sh_fixed += b
        out["shared"] = {"fields": sh_fields,
                         "bytes": sh_per_host * H + sh_fixed,
                         "per_host": sh_per_host,
                         "fixed_bytes": sh_fixed}

    out["per_host"] = (out["hosts"]["per_host"] + out["hp"]["per_host"]
                       + sh_per_host)
    out["fixed_bytes"] = sh_fixed
    out["bytes"] = (out["hosts"]["bytes"] + out["hp"]["bytes"]
                    + (out["shared"]["bytes"] if sh is not None else 0))
    return out


# --- compiled-program capture ----------------------------------------------

# scope -> the latest analysis dict observed for that compiled program
# (process-wide, kept unconditionally like serving.aotcache.STATS: one
# small dict per compile, never per call)
CAPTURED: dict = {}


def _cost_dict(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def observe_executable(scope: str, compiled, donated=()) -> dict:
    """Record one compiled program's XLA cost/memory analyses.

    Returns (and stores in :data:`CAPTURED` under `scope`) a dict::

        {"scope", "available",           # any analysis succeeded
         "flops", "bytes_accessed",      # cost_analysis (or None)
         "argument_bytes", "output_bytes", "temp_bytes",
         "alias_bytes", "generated_code_bytes",  # memory_analysis
         "errors": {...}}                # per-analysis failure text

    Backends/executables that refuse an analysis (older jax, loaded
    disk-cache entries, TPU variants) record the error and carry None
    for those figures — graceful absence, never an exception (the
    contract tests/test_memscope.py pins). Publishes ``cost.*`` /
    ``mem.xla_*`` gauges when metrics are enabled and a
    ``memscope.analyze`` span when tracing is."""
    out = {"scope": scope, "available": False, "flops": None,
           "bytes_accessed": None, "argument_bytes": None,
           "output_bytes": None, "temp_bytes": None,
           "alias_bytes": None, "generated_code_bytes": None,
           # the DECLARED donation (core.jitcache.AotJit's
           # donate_argnums) — the donation audit compares it against
           # the MEASURED alias_bytes per executable
           "donated": tuple(donated or ()),
           "errors": {}}
    if compiled is None:
        out["errors"]["compiled"] = "no executable"
        CAPTURED[scope] = out
        return out
    from . import trace as TR
    if TR.ENABLED:
        tok = TR.TRACER.begin("memscope.analyze", scope=scope)
    try:
        ca = _cost_dict(compiled)
        flops = ca.get("flops")
        ba = ca.get("bytes accessed")
        out["flops"] = float(flops) if flops is not None else None
        out["bytes_accessed"] = float(ba) if ba is not None else None
        out["available"] = True
    except Exception as e:
        out["errors"]["cost_analysis"] = f"{type(e).__name__}: {e}"
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            raise ValueError("backend returned no memory analysis")
        for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                          ("output_bytes", "output_size_in_bytes"),
                          ("temp_bytes", "temp_size_in_bytes"),
                          ("alias_bytes", "alias_size_in_bytes"),
                          ("generated_code_bytes",
                           "generated_code_size_in_bytes")):
            out[key] = int(getattr(ma, attr))
        out["available"] = True
    except Exception as e:
        out["errors"]["memory_analysis"] = f"{type(e).__name__}: {e}"
    CAPTURED[scope] = out
    if TR.ENABLED:
        TR.TRACER.end(tok, available=out["available"])
    from . import metrics as MT
    if MT.ENABLED:
        reg = MT.REGISTRY
        reg.counter("memscope.programs").inc()
        if out["flops"] is not None:
            reg.gauge("cost.flops").set(out["flops"])
        if out["bytes_accessed"] is not None:
            reg.gauge("cost.bytes_accessed").set(out["bytes_accessed"])
        for key in ("argument_bytes", "output_bytes", "temp_bytes",
                    "generated_code_bytes"):
            if out[key] is not None:
                reg.gauge(f"mem.xla_{key}").set(out[key])
    return out


def program_footprint(analysis: dict) -> int | None:
    """The executable's device footprint in bytes — arguments + temp
    + outputs, minus what aliases the inputs (donation) — or None when
    the backend refused memory_analysis. This is the figure the
    capacity planner validates its census prediction against."""
    if not analysis or analysis.get("argument_bytes") is None:
        return None
    return (analysis["argument_bytes"] + analysis["temp_bytes"]
            + analysis["output_bytes"] - analysis["alias_bytes"])


def donation_audit(captured: dict = None) -> list:
    """Donation/aliasing audit over the captured executables (lever 4
    of the shrink campaign): one row per scope comparing the DECLARED
    donation (AotJit donate_argnums, recorded at build time) against
    the MEASURED ``alias_bytes`` from XLA memory_analysis. Flags:

    - ``ok``          — donation declared and XLA aliased bytes;
    - ``inert``       — donation declared but XLA aliased nothing
      (the backend refused the alias: outputs double-buffer and the
      program peaks ~2x its arguments — worth chasing per backend);
    - ``undonated``   — no donation declared on a program whose
      outputs could alias (output_bytes > 0): the state copy is paid
      every call;
    - ``unmeasured``  — the backend refused memory_analysis.

    Sorted fattest-arguments first, so the top row is the biggest
    lever. Rows are plain dicts (capacity_plan renders them)."""
    rows = []
    for scope, an in (captured if captured is not None
                      else CAPTURED).items():
        arg = an.get("argument_bytes")
        if arg is None:
            rows.append({"scope": scope, "flag": "unmeasured",
                         "declared": list(an.get("donated") or ()),
                         "argument_bytes": None, "alias_bytes": None,
                         "temp_bytes": None, "output_bytes": None,
                         "aliased_frac": None})
            continue
        alias = an.get("alias_bytes") or 0
        declared = list(an.get("donated") or ())
        if declared:
            flag = "ok" if alias > 0 else "inert"
        else:
            flag = "undonated" if (an.get("output_bytes") or 0) > 0 \
                else "ok"
        rows.append({
            "scope": scope, "flag": flag, "declared": declared,
            "argument_bytes": int(arg),
            "alias_bytes": int(alias),
            "temp_bytes": int(an.get("temp_bytes") or 0),
            "output_bytes": int(an.get("output_bytes") or 0),
            "aliased_frac": round(alias / arg, 4) if arg else None,
        })
    rows.sort(key=lambda r: -(r["argument_bytes"] or 0))
    return rows


# --- live watermarks -------------------------------------------------------

def rss_bytes() -> int:
    """This process's LIFETIME peak resident set (ru_maxrss is KiB on
    Linux) — monotone over the whole process, so only an upper bound
    for any single run inside it."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def current_rss_bytes() -> int:
    """This process's CURRENT resident set (/proc/self/statm) — what
    per-run high-water sampling maxes over. ru_maxrss would be wrong
    here: it is process-lifetime-monotonic, so in a multi-run process
    (bench.py's 4-config matrix) a small scenario benched after a
    large one would record the large one's peak as its own and poison
    the ledger's memory trajectory. Falls back to the lifetime figure
    where /proc is unavailable."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
    except (OSError, ValueError, IndexError):
        return rss_bytes()


class Watermark:
    """Device-buffer high-water sampling, one sample() per window
    chunk. Uses the backend's per-device ``memory_stats()`` where
    available (TPU/GPU: real HBM in use, per device — under a mesh
    each device is one shard, so ``per_device`` IS the per-shard
    watermark); backends without it (CPU) fall back to process RSS,
    honestly labeled ``source: "rss"``. A TPU without usable
    ``memory_stats()`` is an error, never an RSS stand-in. Sampling is a handful of
    host-side reads per chunk — never a device sync.

    The peak is PER-RUN: the max over this instance's samples of the
    CURRENT usage (``bytes_in_use`` / /proc VmRSS), not the
    allocator's or kernel's lifetime-monotonic peak counters — those
    would contaminate later runs of a multi-run process with earlier
    runs' peaks, exactly the cross-talk the ledger's per-scenario
    memory gate cannot tolerate. The lifetime figures still ride the
    snapshot as ``lifetime_peak_bytes`` for context."""

    def __init__(self, devices=None):
        # devices: the run's device list in shard order
        # (parallel.shard.mesh_local_devices for a mesh; default all
        # local devices). Resolved lazily so constructing a Watermark
        # never imports jax in headless contexts.
        self._devices = devices
        self._probed = False
        self._device_ok = False
        self.source = "rss"
        self.per_device: list = []
        self.peak_bytes = 0
        self.lifetime_peak_bytes = 0
        self.baseline_bytes = 0
        self.samples = 0

    def _probe(self):
        self._probed = True
        if self._devices is None:
            try:
                import jax
                self._devices = jax.local_devices()
            except Exception:
                self._devices = []
        try:
            st = (self._devices[0].memory_stats()
                  if self._devices else None)
        except Exception:
            st = None
        self._device_ok = bool(st) and "bytes_in_use" in st
        if (not self._device_ok and self._devices
                and self._devices[0].platform == "tpu"):
            # the TPU reports its HBM; host RSS in its place would be
            # a wrong number under the device's name
            raise RuntimeError(
                "memscope: memory_stats() gave no bytes_in_use on "
                f"{self._devices[0].device_kind} ({st!r})")
        self.source = "device" if self._device_ok else "rss"
        self.per_device = [0] * (len(self._devices)
                                 if self._device_ok else 0)
        self.baseline_bytes = (self._device_sample()
                               if self._device_ok
                               else current_rss_bytes())

    def _device_sample(self) -> int:
        total = 0
        for i, d in enumerate(self._devices):
            try:
                st = d.memory_stats() or {}
            except Exception:
                st = {}
            cur = int(st.get("bytes_in_use", 0))
            if cur > self.per_device[i]:
                self.per_device[i] = cur
            total += self.per_device[i]
            life = int(st.get("peak_bytes_in_use", cur))
            if life > self.lifetime_peak_bytes:
                self.lifetime_peak_bytes = life
        return total

    def sample(self) -> int:
        """Take one sample; returns the running per-run peak in
        bytes."""
        if not self._probed:
            self._probe()
        if self._device_ok:
            cur = self._device_sample()
        else:
            cur = current_rss_bytes()
            life = rss_bytes()
            if life > self.lifetime_peak_bytes:
                self.lifetime_peak_bytes = life
        if cur > self.peak_bytes:
            self.peak_bytes = cur
        self.samples += 1
        return self.peak_bytes

    def snapshot(self) -> dict:
        """The watermark record SimReport.memory / the tracker / the
        ledger read. ``peak_bytes`` is this run's high water (max of
        current-usage samples — comparable run to run even inside one
        process); ``delta_bytes`` subtracts the pre-run baseline;
        ``lifetime_peak_bytes`` is the monotone process/allocator
        figure, context only, never gated."""
        if not self._probed:
            self.sample()
        return {
            "source": self.source,
            "peak_bytes": int(self.peak_bytes),
            "baseline_bytes": int(self.baseline_bytes),
            "delta_bytes": int(max(self.peak_bytes
                                   - self.baseline_bytes, 0)),
            # clamped to >= the per-run peak: ru_maxrss and /proc
            # statm disagree by a few pages (kernel accounting
            # granularity), and the documented lifetime >= run
            # invariant should hold for consumers
            "lifetime_peak_bytes": int(max(self.lifetime_peak_bytes,
                                           self.peak_bytes)),
            "per_device": (list(self.per_device)
                           if self._device_ok else None),
            "samples": self.samples,
        }


def publish(registry, watermark: dict = None, census: dict = None,
            xla: dict = None) -> None:
    """Expose a run's memory figures as ``mem.*`` gauges — the
    metrics.json ``memory`` section (obs.metrics assembles it from
    this prefix, like the ``perf`` section)."""
    if watermark:
        registry.gauge("mem.peak_bytes").set(watermark["peak_bytes"])
        registry.gauge("mem.delta_bytes").set(watermark["delta_bytes"])
        if watermark.get("per_device"):
            for i, v in enumerate(watermark["per_device"]):
                registry.gauge(f"mem.device_peak_bytes.{i}").set(v)
    if census:
        registry.gauge("mem.state_bytes").set(census["bytes"])
        registry.gauge("mem.state_bytes_per_host").set(
            census["per_host"])
        registry.gauge("mem.hot_state_bytes").set(
            census["hosts"]["hot"]["runtime_bytes"])
    if xla:
        for key in ("bytes_accessed", "flops"):
            if xla.get(key) is not None:
                registry.gauge(f"cost.{key}").set(xla[key])
        for key in ("argument_bytes", "output_bytes", "temp_bytes",
                    "generated_code_bytes"):
            if xla.get(key) is not None:
                registry.gauge(f"mem.xla_{key}").set(xla[key])
