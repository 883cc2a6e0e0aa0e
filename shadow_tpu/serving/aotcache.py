"""Persistent AOT executable cache: the disk tier under AotJit.

``core.jitcache.AotJit`` already memoizes one Compiled object per
argument signature — but only in process memory, so every fresh
process pays the full XLA build (312 s on a cold tor50k CPU run,
10-15 min per config shape on chip — BASELINE.md). This module adds
the disk tier: executables serialized via
``jax.experimental.serialize_executable`` and reloaded by any later
process that asks for the same program.

Cache key anatomy (docs/serving.md) — an entry may load ONLY when all
of these match, so a stale executable is structurally unreachable:

- the AotJit's ``cache_scope``: a stable program identity carrying
  the config fingerprint (``obs.ledger.fingerprint_of(cfg)``) and the
  chunk size — e.g. ``run_windows.c64.<fp16>``;
- the argument signature (``AotJit._sig``: pytree structure, leaf
  shapes/dtypes/weak-types, shardings);
- jax/jaxlib versions and the backend's own platform_version (XLA);
- the platform: backend name, device kind, device count;
- a source digest over every traced module
  (``shadow_tpu/{core,engine,net,apps,parallel,hosting}``): editing
  device code invalidates every entry mechanically, no version bump
  to forget.

Donation policy: cached programs compile, store and execute their
DONATION-FREE twin (``AotJit.undonated_jit``). A serialize round trip
of a donated executable is unsound on the XLA:CPU client — the loaded
executable's outputs alias the donated input buffers, whose memory
the runtime frees, a use-after-free that silently corrupts results
once the allocator reuses the block. Undonated execution computes
identical values (digest chains stay byte-identical, proven in
tests/test_serving.py) at a transient 2x peak for the donated
operands during each call; runs without an active cache keep
donation untouched.

The ``.meta.json`` sidecar also records the ids of the devices the
program was compiled for; a load hands exactly those devices to
``deserialize_and_load`` (``execution_devices``), so a one-chip program
reloads as a one-chip program on a host with several.

Storage is crash-safe in the PR 5 checkpoint-store shape: sidecars
(``.sha256`` content hash, ``.meta.json`` key anatomy) publish before
the payload's atomic tmp+fsync+os.replace, loads verify the hash and
fall back LOUDLY to recompile on any torn/corrupt/alien entry, and
retention bounds the directory. Serialization support is probed once
per process (``serialize_support``); backends without it degrade to
the in-memory tier with a warning, never an error.

Observability: every disk hit / miss / store / reject counts in
:data:`STATS` (always) and ``jitcache.*`` metrics (when obs.metrics
is enabled), and the compile/load walls record as ``jitcache.compile``
/ ``jitcache.load`` spans — which obs.perf attributes to the
``compile-miss`` / ``compile-hit`` phases, so a phase map says
mechanically whether "cold" included a real XLA build.

Enable with ``--aot-cache DIR`` (CLI), ``fleet run --aot-cache DIR``,
or ``SHADOW_TPU_AOT_CACHE=DIR``.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import sys
import time

FORMAT = "shadow_tpu.serving.aotcache"
VERSION = 2

# entries retained per cache dir (oldest-mtime pruned past this);
# SHADOW_TPU_AOT_CACHE_KEEP overrides
DEFAULT_KEEP = 64

# process-wide tallies, kept unconditionally (bench.py labels each
# line compile_cache=hit|miss from the `compiles` delta; the metrics
# registry mirrors them when enabled)
STATS = {"compiles": 0, "disk_hits": 0, "disk_misses": 0,
         "disk_stores": 0, "rejected": 0,
         "compile_wall_s": 0.0, "load_wall_s": 0.0}

ACTIVE = None
_ENV_CHECKED = False

_REPO_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the packages whose source the compiled programs trace — the window/
# exchange/app programs (core/engine/net/apps/parallel) AND the hosted
# op-replay program (hosting.bridge apply_ops, cache_scope
# "apply_ops"); editing any of them invalidates every cache entry
SOURCE_SCOPE = ("core", "engine", "net", "apps", "parallel", "hosting")


def _warn(msg: str):
    sys.stderr.write(f"shadow_tpu: aot-cache: {msg}\n")


def install(root: str, keep: int = None) -> "DiskCache":
    """Enable the disk tier process-wide (the obs.install contract:
    the installer owns the lifecycle; AotJit just consults active())."""
    global ACTIVE
    ACTIVE = DiskCache(root, keep=keep)
    return ACTIVE


def uninstall():
    global ACTIVE, _ENV_CHECKED
    ACTIVE = None
    _ENV_CHECKED = True      # tests: do not fall back to the env var


def active() -> "DiskCache | None":
    """The installed cache, resolving SHADOW_TPU_AOT_CACHE once per
    process so fleet children enable the tier without CLI plumbing."""
    global ACTIVE, _ENV_CHECKED
    if ACTIVE is None and not _ENV_CHECKED:
        _ENV_CHECKED = True
        env = os.environ.get("SHADOW_TPU_AOT_CACHE")
        if env:
            install(env)
    return ACTIVE


# --- capability probe ------------------------------------------------------

_SERIALIZE_OK = None


def serialize_support() -> bool:
    """Once per process: can this backend serialize AND reload a
    compiled executable? Probed on a trivial program; a backend
    without support (or a jax without the API) degrades the cache to
    in-memory-only with a loud warning — never an error."""
    global _SERIALIZE_OK
    if _SERIALIZE_OK is None:
        try:
            import jax
            import jax.numpy as jnp
            from jax.experimental import serialize_executable as se
            c = jax.jit(lambda x: x + 1).lower(jnp.int32(0)).compile()
            payload, in_tree, out_tree = se.serialize(c)
            se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=c.runtime_executable().local_devices())
            _SERIALIZE_OK = True
        except Exception as e:
            _SERIALIZE_OK = False
            _warn("this backend cannot serialize executables "
                  f"({type(e).__name__}: {e}); the AOT cache is "
                  "in-memory only for this process — fresh processes "
                  "will recompile")
    return _SERIALIZE_OK


# --- key components --------------------------------------------------------

_SOURCE_DIGEST = None


def source_digest() -> str:
    """sha256 over every .py under the traced packages (sorted
    relpaths, name + content), computed once per process. Any device-
    code edit changes it, so no stale executable can survive a source
    change."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        h = hashlib.sha256()
        files = []
        for pkg in SOURCE_SCOPE:
            root = os.path.join(_REPO_PKG, pkg)
            for dirpath, _, names in os.walk(root):
                for n in names:
                    if n.endswith(".py"):
                        p = os.path.join(dirpath, n)
                        files.append((os.path.relpath(p, _REPO_PKG), p))
        for rel, p in sorted(files):
            h.update(rel.encode())
            with open(p, "rb") as f:
                h.update(f.read())
        _SOURCE_DIGEST = h.hexdigest()[:16]
    return _SOURCE_DIGEST


def platform_key() -> dict:
    """The environment components of the key: an executable compiled
    by a different jax/jaxlib/XLA, backend, device kind or device
    count must MISS (stale-rejection is structural — the key differs,
    so the entry is unreachable, never loaded-and-wrong)."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    try:
        xla = dev.client.platform_version
    except Exception:
        xla = "?"
    return {"backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", "?"),
            "n_devices": jax.device_count(),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "xla": xla}


def _sig_text(sig) -> str:
    """Stable textual form of an AotJit._sig value (the in-memory key
    may hold live sharding objects whose repr is process-stable but
    whose hash is not portable; the disk key needs text)."""
    treedef, leaves = sig
    return json.dumps([str(treedef),
                       [[list(shape), dtype, bool(weak), str(sh)]
                        for shape, dtype, weak, sh in leaves]])


def entry_key(scope: str, sig) -> str:
    """One disk-entry key from all five components; the .meta.json
    sidecar records the anatomy for post-mortems. ``donated: False``
    records that stored executables are always the donation-free
    twin (load_or_compile) — a future donated artifact would be a
    different key, never a silent swap."""
    blob = json.dumps({"format": FORMAT, "version": VERSION,
                       "scope": scope, "sig": _sig_text(sig),
                       "platform": platform_key(),
                       "source": source_digest(),
                       "donated": False}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def key_meta(scope: str, sig) -> dict:
    return {"format": FORMAT, "version": VERSION, "scope": scope,
            "sig": _sig_text(sig), "platform": platform_key(),
            "source": source_digest(), "donated": False}


# --- the disk tier ---------------------------------------------------------

def _write_atomic(path: str, data: bytes):
    """tmp + fsync + os.replace (the checkpoint-store write shape): a
    crash mid-write can never publish a torn file."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class DiskCache:
    """One cache directory of serialized executables."""

    def __init__(self, root: str, keep: int = None):
        self.root = root
        if keep is None:
            keep = int(os.environ.get("SHADOW_TPU_AOT_CACHE_KEEP",
                                      str(DEFAULT_KEEP)))
        self.keep = max(int(keep), 1)

    def exec_path(self, key: str) -> str:
        return os.path.join(self.root, key + ".exec")

    def meta_path(self, key: str) -> str:
        return os.path.join(self.root, key + ".meta.json")

    def has(self, key: str) -> bool:
        return os.path.exists(self.exec_path(key))

    def entries(self) -> list:
        """Cached keys, oldest mtime first."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        paths = [os.path.join(self.root, n) for n in names
                 if n.endswith(".exec")]

        def mtime(p):
            try:
                return os.path.getmtime(p)
            except OSError:
                return 0.0
        return [os.path.basename(p)[:-len(".exec")]
                for p in sorted(paths, key=lambda p: (mtime(p), p))]

    def load(self, key: str):
        """-> a loaded Compiled, or None (miss). EVERY failure mode —
        missing entry, missing/mismatched hash sidecar, unpicklable
        payload, a backend that refuses the executable — is a miss
        that falls back to recompile; corrupt entries warn and are
        removed so they cannot re-fail every run."""
        if not serialize_support():
            return None
        p = self.exec_path(key)
        try:
            with open(p, "rb") as f:
                blob = f.read()
        except OSError:
            STATS["disk_misses"] += 1
            return None
        try:
            with open(p + ".sha256") as f:
                want = f.read().strip()
        except OSError:
            want = None
        if want is None or hashlib.sha256(blob).hexdigest() != want:
            self._reject(key, "content hash missing or mismatched "
                         "(torn write / bit rot)")
            return None
        try:
            with open(self.meta_path(key)) as f:
                ids = json.load(f)["device_ids"]
            import jax
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in ids]
        except (OSError, ValueError, KeyError, TypeError) as e:
            self._reject(key, f"no usable device_ids in the meta "
                         f"({type(e).__name__}: {e})")
            return None
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = pickle.loads(blob)
            # the program's own devices: without them jax reloads it
            # for every device of the backend, and a one-chip program
            # then expects one shard per device
            return se.deserialize_and_load(payload, in_tree, out_tree,
                                           execution_devices=devices)
        except Exception as e:
            self._reject(key, f"deserialize failed "
                         f"({type(e).__name__}: {e})")
            return None

    def _reject(self, key: str, why: str):
        STATS["rejected"] += 1
        _warn(f"entry {key}: {why} — falling back to recompile and "
              "dropping the entry")
        self.remove(key)

    def remove(self, key: str):
        for p in (self.exec_path(key), self.exec_path(key) + ".sha256",
                  self.meta_path(key)):
            try:
                os.unlink(p)
            except OSError:
                pass

    # a publisher holding the per-key lock longer than this is
    # presumed dead (SIGKILL mid-store) and its lock is broken
    LOCK_STALE_S = 600.0

    def _publish_lock(self, key: str):
        """O_EXCL per-key writer lock -> fd, or None (another LIVE
        writer is publishing this key — first writer wins; the loser
        keeps its in-memory executable). Concurrent same-key stores
        are real under `fleet run --aot-cache` WITHOUT --prewarm
        (every child finishes the same compile at ~the same time),
        and unserialized sidecar/payload interleavings would look
        like corruption to every reader — which then DELETES the
        half-published entry."""
        lock = self.exec_path(key) + ".lock"
        for _ in range(2):
            try:
                return os.open(lock, os.O_CREAT | os.O_EXCL
                               | os.O_WRONLY), lock
            except FileExistsError:
                try:
                    if (time.time() - os.path.getmtime(lock)
                            < self.LOCK_STALE_S):
                        return None
                    os.unlink(lock)        # stale: dead writer
                except OSError:
                    return None
        return None

    def store(self, key: str, compiled, meta: dict = None) -> str | None:
        """Serialize + publish one executable. Sidecars (hash, meta)
        publish BEFORE the payload's atomic replace, so a visible
        .exec always has its verification hash (the PR 5 ordering —
        a kill between the two writes leaves an invisible entry, not
        a complete-looking unverifiable one). Publishing is
        first-writer-wins: a complete entry is never overwritten, and
        a per-key lock serializes racing writers (fleet children
        compiling the same shape), since interleaved sidecar/payload
        writes from two processes would read as corruption."""
        if not serialize_support():
            return None
        os.makedirs(self.root, exist_ok=True)
        if self.has(key):
            return None           # someone already published it whole
        got = self._publish_lock(key)
        if got is None:
            return None
        fd, lock = got
        try:
            if self.has(key):
                return None
            try:
                from jax.experimental import serialize_executable as se
                payload, in_tree, out_tree = se.serialize(compiled)
                blob = pickle.dumps((payload, in_tree, out_tree))
            except Exception as e:
                _warn(f"serialize failed ({type(e).__name__}: {e}); "
                      "entry not persisted (this process keeps its "
                      "in-memory executable)")
                return None
            p = self.exec_path(key)
            _write_atomic(p + ".sha256",
                          (hashlib.sha256(blob).hexdigest()
                           + "\n").encode())
            m = dict(meta or {})
            m["payload_bytes"] = len(blob)
            m["device_ids"] = [d.id for d in
                               compiled.runtime_executable().local_devices()]
            _write_atomic(self.meta_path(key),
                          (json.dumps(m, indent=1, sort_keys=True)
                           + "\n").encode())
            _write_atomic(p, blob)
            STATS["disk_stores"] += 1
            self._retain()
            return p
        finally:
            os.close(fd)
            try:
                os.unlink(lock)
            except OSError:
                pass

    def _retain(self):
        keys = self.entries()
        for key in keys[:max(len(keys) - self.keep, 0)]:
            self.remove(key)


# --- the AotJit miss path --------------------------------------------------

def load_or_compile(jitted, scope, sig, args, undonated=None):
    """Resolve one AotJit signature miss: disk-load where a cache is
    active and the program has a stable scope, else compile (and
    persist). The observability contract lives here so every AotJit
    user gets it for free: ``jitcache.load`` / ``jitcache.compile``
    spans (-> obs.perf ``compile-hit`` / ``compile-miss`` phases),
    ``jitcache.*`` metrics, and the unconditional :data:`STATS`.

    `undonated` is a zero-arg callable returning the donation-free
    twin of `jitted` (None when the program donates nothing). When
    the disk tier is in play, the UNDONATED program is what compiles,
    stores and loads: a serialize round trip of a donated executable
    is unsound on the XLA:CPU client — the loaded executable's
    outputs alias the donated input buffers, whose memory the runtime
    frees, a use-after-free that silently corrupts results once the
    allocator reuses the block (reproduced on the window chunk
    program: event-queue bytes mutate after unrelated allocations).
    Undonated execution computes identical values — cold-through-
    cache and warm chains stay byte-identical to the donated no-cache
    run (tests/test_serving.py) — at a transient 2x peak for the
    donated operands during each call. Without an active cache (or
    without a scope) the donated program runs untouched."""
    from ..obs import metrics as MT
    from ..obs import trace as TR

    cache = active()
    key = None
    if cache is not None and scope is not None and serialize_support():
        # the swap only buys anything when executables actually
        # round-trip through disk; a backend that cannot serialize
        # keeps donation (and its memory savings) untouched
        if undonated is not None:
            u = undonated()
            if u is not None:
                jitted = u
        key = entry_key(scope, sig)
        # a load is known to be one only once it hits: the span is
        # recorded from its stamp then, in the JSON timeline alone
        t0 = TR.TRACER.now() if TR.ENABLED else None
        w0 = time.perf_counter()
        fn = cache.load(key)
        if fn is not None:
            wall = time.perf_counter() - w0
            STATS["disk_hits"] += 1
            STATS["load_wall_s"] += wall
            if TR.ENABLED:
                TR.TRACER.complete("jitcache.load", t0,
                                   args={"key": key, "scope": scope})
            if MT.ENABLED:
                reg = MT.REGISTRY
                reg.counter("jitcache.disk_hits").inc()
                g = reg.gauge("jitcache.load_wall_s")
                g.set(g.v + wall)
            return fn
    if TR.ENABLED:
        tok = TR.TRACER.begin("jitcache.compile", scope=scope or "?",
                              cached=key is not None)
    w0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    wall = time.perf_counter() - w0
    STATS["compiles"] += 1
    STATS["compile_wall_s"] += wall
    if TR.ENABLED:
        TR.TRACER.end(tok)
    if MT.ENABLED:
        reg = MT.REGISTRY
        reg.counter("jitcache.compiles").inc()
        g = reg.gauge("jitcache.compile_wall_s")
        g.set(g.v + wall)
    if key is not None:
        cache.store(key, compiled, meta=key_meta(scope, sig))
    return compiled
