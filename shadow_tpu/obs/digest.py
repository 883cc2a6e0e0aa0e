"""Determinism flight recorder: windowed state digests + run manifest.

Shadow's core contract — kept by this repro (PAPER.md; the fault
injector's "dual same-seed runs bit-identical, hosted children
included") — is deterministic discrete-event execution. Nothing else in
the repo continuously *verifies* that contract, and a broken guarantee
surfaces only as a silently different SimReport. This module turns
"the runs differ" into "window 412, section tcp, host 17": a cheap,
configurable-cadence recorder that hashes the engine's device state at
window-chunk boundaries (and at every fault boundary and at the end of
the run) and appends one JSON line per sample to a *digest chain* —
each record carries a running chain hash over everything before it, so
two chains are comparable record by record and the first divergent
window is pinned by `tools/divergence.py`.

What gets hashed, per record:

- every `engine.state.Hosts` array, pulled once from the device
  (`engine.checkpoint.named_leaves` — the same leaf set checkpoints
  serialize; one device→host transfer per cadence, nothing added to
  the compiled programs), grouped into named *sections* (event_queue,
  tcp, nic,
  outbox, rng, app, stats, ... — `engine.state.STATE_SECTIONS`);
- the hosted-channel op stream: the running hash of every op batch
  `hosting.runtime` applied and of every shim protocol request each
  hosted child issued (`hosting.shim`), so a divergence ATTRIBUTES to
  "the hosted child behaved differently" vs "the engine diverged";
- optionally (host count <= `host_detail`) one short digest per host
  row, so divergence reports name the first divergent host.

Dead-slot canonicalization: freed event-queue slots, outbox tails,
NIC-ring tails and closed socket rows legitimately retain stale bytes
that can differ between semantically identical runs (e.g. the sharded
vs single-chip exchange). `engine.window.canonicalize_state` zeroes
them host-side before hashing, so the digest chain is a statement
about LIVE state — identical across 1-chip and mesh runs, extending
test_parallel's v1≡v2 claim.

A companion ``<path>.manifest.json`` captures seed, scenario
fingerprint, engine config, CLI args, versions, platform and git rev,
so any two chains are comparable (and `tools/divergence.py --bisect`
can replay the runs at cadence 1 to pin the exact window).

Cheap when disabled: the module-level ``ENABLED`` boolean is the whole
cost (the obs.trace/obs.metrics contract); hot paths guard with
``if digest.ENABLED:``. Enabled cost is one state pull + one linear
hash pass per cadence, accounted as a ``digest.record`` span
(obs.trace) around its two parts, ``digest.pull`` (the device→host
transfer; its ``bytes`` arg counts what moved) and ``digest.hash``
(canonicalise, hash, chain), and ``digest.*`` metrics when those
recorders are on.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import numpy as np

from . import trace as TR

ENABLED = False
RECORDER = None

# default cadence, in windows: one record per default-sized window
# chunk (EngineConfig.chunk_windows), so recording never forces extra
# host round-trips on a default run
DEFAULT_EVERY = 64

# per-host digests are recorded only up to this host count (the O(H)
# python-loop hashing is real at 100k hosts; section digests still
# localize divergence there). SHADOW_TPU_DIGEST_HOSTS overrides.
DEFAULT_HOST_DETAIL = 1024

_CHAIN_SEED = b"shadow_tpu.digest.v1"


def _hash_arrays(arrs: dict, H: int, host_detail: int):
    """-> (sections hex dict, per-host hex list or None, bytes hashed).

    `arrs` maps field name -> canonicalized [:H] numpy array, in
    engine.state.Hosts field order (insertion order preserved). Each
    section hash covers field name, dtype, shape and raw bytes, so a
    layout change can never alias a value change.
    """
    from ..engine.state import section_of

    sections = {}
    host_hashers = ([hashlib.blake2b(digest_size=4) for _ in range(H)]
                    if 0 < H <= host_detail else None)
    nbytes = 0
    for name, a in arrs.items():
        if a.size == 0:
            # zero-capacity column (a disabled config-gated feature,
            # e.g. netscope off allocates ns_hist with a zero bucket
            # axis): skip it entirely — header included — so chains
            # from disabled runs stay byte-identical to chains
            # recorded before the column existed. No enabled feature
            # allocates at zero (rings use max(cap, 1)), so a real
            # value change can never hide here.
            continue
        sec = sections.get(section_of(name))
        if sec is None:
            sec = sections[section_of(name)] = hashlib.blake2b(
                digest_size=8)
        sec.update(f"{name}:{a.dtype.str}:{a.shape}".encode())
        buf = np.ascontiguousarray(a)
        sec.update(buf)
        nbytes += buf.nbytes
        if host_hashers is not None:
            for i in range(H):
                host_hashers[i].update(buf[i])
    out = {k: h.hexdigest() for k, h in sorted(sections.items())}
    hosts_hex = ([h.hexdigest() for h in host_hashers]
                 if host_hashers is not None else None)
    return out, hosts_hex, nbytes


class DigestRecorder:
    """One digest chain. `path=None` collects in memory only (tests).
    `writer=False` runs the full cadence/chain state machine but never
    touches the filesystem — non-zero processes of a multi-process
    mesh stay in lockstep with process 0 (every process must agree on
    when a record is due, because the state pull is a collective)."""

    def __init__(self, path: str | None, every: int = DEFAULT_EVERY,
                 host_detail: int = None, context: dict = None,
                 writer: bool = True):
        self.path = path
        self.every = max(int(every), 1)
        if host_detail is None:
            host_detail = int(os.environ.get(
                "SHADOW_TPU_DIGEST_HOSTS", str(DEFAULT_HOST_DETAIL)))
        self.host_detail = host_detail
        # CLI context (argv, config path) folded into the manifest by
        # the installer — engine.sim fills the run-derived fields
        self.context = dict(context or {})
        self.records = []
        self.manifest = None
        self.bytes_hashed = 0
        self.writer = bool(writer)
        self._chain = _CHAIN_SEED
        self._file = None
        self._mode = "w"
        self.next_due = self.every

    # --- cadence ---
    def due(self, total_windows: int) -> bool:
        return total_windows >= self.next_due

    def begin_run(self, total_windows: int):
        """Re-arm the cadence for a (re)starting run. One recorder may
        span several runs (an outer harness extending one chain), but
        each run's window counter restarts at 0 — or jumps, on resume —
        so the clock left by a previous run's last record would
        suppress every cadence sample of the next run."""
        self.next_due = int(total_windows) + self.every

    @property
    def chain_hex(self) -> str:
        """Current running chain hash — stamped into checkpoints
        (engine.checkpoint ``__digest_chain__``) so rewind() can
        verify the kept prefix refolds to exactly the snapshot's
        position."""
        return self._chain.hex()

    def rewind(self, n_records: int, chain_hex: str = None):
        """Resume a chain a crashed attempt left behind: reload the
        chain file and keep EXACTLY the first `n_records` records —
        the count the checkpoint stamped at save time — dropping
        everything later (records past the snapshot die with the
        crash and are re-produced live by the resumed run; the
        determinism contract makes the kept prefix identical to what
        this run would have written). The kept prefix is refolded and
        verified against the snapshot's `chain_hex`; the cadence
        re-arms exactly as the uninterrupted run's was (every record
        sets next_due = its window + every). Later records APPEND to
        the truncated file: the final chain is byte-identical to an
        uninterrupted same-seed run's (tests/test_until_complete.py).

        A trailing torn line (the crash landed mid-write) never
        matters — it is past the kept count; a kept record that does
        not refold is a corrupted prefix and fails loud.

        Multi-process meshes: EVERY process runs rewind (all must
        refold the same prefix and re-arm the same cadence — the
        per-record state pull is a collective), reading the chain
        file over the same shared storage the snapshot came from;
        only the writer (process 0) truncates, via an atomic
        os.replace, so a peer reading concurrently sees the kept
        prefix either way. This is what lifted the PR 5
        resume+digest+multi-process gate."""
        n = max(int(n_records), 0)
        kept = []
        if self.path is not None and os.path.exists(self.path):
            assert self._file is None, "rewind() must precede records"
            with open(self.path) as f:
                lines = f.read().splitlines()
            for i, line in enumerate(lines):
                if len(kept) >= n:
                    break
                if not line.strip():
                    continue
                try:
                    kept.append(json.loads(line))
                except json.JSONDecodeError:
                    raise ValueError(
                        f"digest chain {self.path}: line {i + 1} is "
                        "corrupt inside the checkpointed prefix "
                        f"({len(kept)}/{n} records); refusing to "
                        "resume it")
        if len(kept) < n:
            raise ValueError(
                f"digest chain {self.path} holds {len(kept)} records "
                f"but the checkpoint was taken after {n} — the chain "
                "file does not belong to this run")
        chain = _CHAIN_SEED
        for rec in kept:
            body = {k: v for k, v in rec.items() if k != "chain"}
            payload = json.dumps(body, sort_keys=True,
                                 separators=(",", ":")).encode()
            chain = hashlib.blake2b(chain + payload,
                                    digest_size=16).digest()
            if rec.get("chain") != chain.hex():
                raise ValueError(
                    f"digest chain {self.path}: record at window "
                    f"{rec.get('window')} does not refold — the "
                    "prefix is corrupted; delete the chain and record "
                    "fresh")
        if chain_hex and chain.hex() != chain_hex:
            raise ValueError(
                f"digest chain {self.path}: the {n}-record prefix "
                "refolds to a different chain hash than the "
                "checkpoint stamped — chain and snapshot are from "
                "different runs")
        self._chain = chain
        self.records = kept
        if self.path is not None and self.writer:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                for rec in kept:
                    f.write(json.dumps(rec, sort_keys=True,
                                       separators=(",", ":")) + "\n")
            os.replace(tmp, self.path)
        self._mode = "a"
        self.next_due = ((kept[-1]["window"] + self.every) if kept
                         else self.every)

    # --- manifest ---
    def manifest_path(self) -> str | None:
        return self.path + ".manifest.json" if self.path else None

    def write_manifest(self, manifest: dict):
        """Record (and persist) the run manifest; first run wins when
        an outer harness holds the recorder open across runs."""
        if self.manifest is not None:
            return
        self.manifest = manifest
        mp = self.manifest_path()
        if mp is not None and self.writer:
            tmp = mp + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
            os.replace(tmp, mp)

    # --- recording ---
    def record(self, hosts, H: int, window: int, sim_ns: int, kind: str,
               hosted: dict = None) -> dict:
        """Hash the device state into one chain record.

        `hosts` is the engine's Hosts pytree (its arrays are pulled to
        the host here — the one device→host transfer per cadence);
        `H` the true host count (mesh padding rows are sliced off so
        sharded chains match single-chip ones); `hosted` the
        hosting-runtime op-stream digests, when hosted apps exist.
        """
        from ..engine.checkpoint import named_leaves
        from ..engine.window import canonicalize_state

        if TR.ENABLED:
            tok = TR.TRACER.begin("digest.pull")
        full = [(name, np.asarray(leaf))
                for name, leaf in named_leaves(hosts)]
        if TR.ENABLED:
            TR.TRACER.end(tok, bytes=sum(a.nbytes for _, a in full))
            tok = TR.TRACER.begin("digest.hash")
        arrs = canonicalize_state({name: a[:H] for name, a in full})
        sections, hosts_hex, nbytes = _hash_arrays(arrs, H,
                                                   self.host_detail)
        self.bytes_hashed += nbytes
        rec = {"window": int(window), "sim_ns": int(sim_ns),
               "kind": kind, "sections": sections}
        if hosted is not None:
            rec["hosted"] = hosted
            h = hashlib.blake2b(
                json.dumps(hosted, sort_keys=True).encode(),
                digest_size=8)
            rec["sections"] = dict(sections, hosted=h.hexdigest())
        if hosts_hex is not None:
            rec["hosts"] = hosts_hex
        payload = json.dumps(rec, sort_keys=True,
                             separators=(",", ":")).encode()
        self._chain = hashlib.blake2b(self._chain + payload,
                                      digest_size=16).digest()
        rec["chain"] = self._chain.hex()
        self.records.append(rec)
        if self.path is not None and self.writer:
            if self._file is None:
                self._file = open(self.path, self._mode)
            self._file.write(json.dumps(rec, sort_keys=True,
                                        separators=(",", ":")) + "\n")
            self._file.flush()
        self.next_due = int(window) + self.every
        if TR.ENABLED:
            TR.TRACER.end(tok)
        return rec

    def close(self):
        if self._file is not None:
            self._file.close()
            self._file = None


def build_manifest(scenario, cfg, seed: int, sh, host_names: list,
                   recorder: DigestRecorder, checkpoint_path: str = None,
                   shards: int = 1, pcap: bool = False,
                   faults: bool = False, hosted: bool = False) -> dict:
    """Everything needed to (a) decide two chains are comparable and
    (b) replay the run for bisection (tools/divergence.py)."""
    import platform as _platform
    import sys as _sys

    import jax

    from ..engine.checkpoint import scenario_fingerprint

    cfgd = dataclasses.asdict(cfg)
    if cfgd.get("app_kinds") is not None:
        cfgd["app_kinds"] = list(cfgd["app_kinds"])
    m = {
        "format": "shadow_tpu.digest.manifest", "version": 1,
        "seed": int(seed),
        "fingerprint": scenario_fingerprint(scenario, cfg, seed),
        "config_path": recorder.context.get(
            "config_path", getattr(scenario, "source_path", None)),
        "argv": recorder.context.get("argv"),
        "stop_time_ns": int(scenario.stop_time),
        "min_jump_ns": int(sh.min_jump),
        "tcp": {"cc_kind": int(sh.cc_kind),
                "init_wnd": float(sh.tcp_init_wnd),
                "ssthresh0": float(sh.tcp_ssthresh0)},
        "hosts": len(host_names),
        "host_names": (list(host_names)
                       if len(host_names) <= recorder.host_detail
                       else None),
        "engine_config": cfgd,
        "digest_every": recorder.every,
        "host_detail": recorder.host_detail,
        "shards": int(shards),
        # run modes that legitimately change digested state or gate
        # checkpoint replay: pcap drains the trace rings chunk-wise
        # (a pcap-only pair diverges in trace_ring — the manifest
        # delta says why), faults/hosted block --use-checkpoint
        "pcap": bool(pcap),
        "faults": bool(faults),
        "hosted": bool(hosted),
        "platform": jax.default_backend(),
        "versions": {"python": _sys.version.split()[0],
                     "jax": jax.__version__,
                     "numpy": np.__version__,
                     "os": _platform.platform()},
        "git_rev": _git_rev(),
        "checkpoint_path": checkpoint_path,
    }
    return m


def _git_rev() -> str | None:
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None
    except Exception:
        return None


def install(path: str | None, every: int = DEFAULT_EVERY,
            host_detail: int = None, context: dict = None,
            writer: bool = True) -> DigestRecorder:
    """Enable digest recording process-wide; the installer owns
    finish() (the obs.trace/obs.metrics contract). `writer=False`
    keeps the full recorder state machine but never writes files —
    the non-zero processes of a multi-process mesh."""
    global ENABLED, RECORDER
    RECORDER = DigestRecorder(path, every=every, host_detail=host_detail,
                              context=context, writer=writer)
    ENABLED = True
    return RECORDER


def finish() -> DigestRecorder | None:
    """Disable recording, close the chain file, return the recorder."""
    global ENABLED, RECORDER
    rec, RECORDER, ENABLED = RECORDER, None, False
    if rec is not None:
        rec.close()
    return rec
