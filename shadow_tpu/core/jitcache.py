"""AOT-compiled jit wrapper.

The ahead-of-time path — ``jit(f).lower(*args).compile()`` then calling
the Compiled object — was first adopted as a workaround: the jax 0.4.37
build this repo started on mis-executed large programs through the jit
dispatch fast path ("Execution supplied N buffers but compiled program
expected M buffers"). On jax 0.9.0 that failure does not reproduce:
test_digest, test_parallel and test_compaction pass with AotJit calls
routed through plain ``jax.jit`` dispatch (PR 21). The wrapper stays
because the persistent executable cache below is built on its Compiled
objects.

:class:`AotJit` wraps a function in exactly that: one Compiled object
per argument-signature (shapes/dtypes/weak-types/shardings), cached.
It costs a small per-call key computation over the arg pytree.

Since PR 13 the memoization has a DISK tier (serving.aotcache): an
AotJit constructed with a stable ``cache_scope`` string resolves a
signature miss by first trying the persistent executable cache (when
one is active — ``--aot-cache DIR`` / ``SHADOW_TPU_AOT_CACHE``), so a
fresh process loads a known program in seconds instead of recompiling
it in minutes. Programs without a stable identity (no cache_scope)
keep the memory-only behavior.
"""

from __future__ import annotations

import os

import jax

# <repo>/.jax_cache (gitignored): a fixed path, because the path is
# part of what makes a later process find the entries again
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache():
    """Place JAX's persistent compilation cache, before the first
    compile; -> the directory in use, or None (uncached).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no other
    directory is set here. Unset: ``<repo>/.jax_cache`` on an
    accelerator. CPU runs without the variable stay uncached (the test
    suite, tests/conftest.py). One definition for the CLI, bench.py,
    tools/baseline_configs.py and chip_smoke.py."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", REPO_COMPILE_CACHE)
    return REPO_COMPILE_CACHE


class AotJit:
    def __init__(self, fn, cache_scope: str = None, **jit_kwargs):
        self._jit = jax.jit(fn, **jit_kwargs)
        self._fn = fn
        self._jit_kwargs = dict(jit_kwargs)
        self._compiled = {}
        # stable program identity for the persistent cache: must
        # change whenever the traced Python would trace differently
        # (closed-over config, chunk size...) — by convention it
        # carries obs.ledger.fingerprint_of(cfg). None = memory only.
        self.cache_scope = cache_scope
        # latest obs.memscope analysis of an executable built through
        # this wrapper (flops / bytes accessed / arg+temp bytes), or
        # None before the first build
        self.analysis = None
        # signature -> {module: {HLO instruction name: op_name}} of its
        # executable (scope_map), or None before the first map
        self._scopes = None

    def undonated_jit(self):
        """The donation-free twin of this program, or None when there
        is nothing to strip. The disk tier executes cached programs
        through this: a serialize/deserialize round trip of a DONATED
        executable is unsound on the XLA:CPU client (the loaded
        executable's outputs alias the donated input buffers, whose
        memory the runtime frees — a use-after-free that corrupts
        results after later allocations; see serving.aotcache).
        Undonated execution computes identical values — donation is
        memory management, never math — at a transient 2x peak for
        the donated operands during the call."""
        if not (self._jit_kwargs.get("donate_argnums")
                or self._jit_kwargs.get("donate_argnames")):
            return None
        kw = {k: v for k, v in self._jit_kwargs.items()
              if k not in ("donate_argnums", "donate_argnames")}
        return jax.jit(self._fn, **kw)

    @staticmethod
    def _sharding_key(sh):
        """The signature's sharding component. Hashable shardings key
        as themselves. An UNHASHABLE sharding must still yield a
        distinct, stable key: the old ``sh = None`` degradation
        aliased two different-sharding signatures onto one executable
        — exactly the wrong-buffers failure mode this class exists to
        prevent. Derive a structural key instead: type, the sorted
        device ids it spans, its string form (NamedSharding spells
        mesh + PartitionSpec there) and the memory kind."""
        if sh is None:
            return None
        try:
            hash(sh)
            return sh
        except TypeError:
            pass
        try:
            devs = tuple(sorted(d.id for d in sh.device_set))
        except Exception:
            devs = None
        return (type(sh).__name__, devs, str(sh),
                getattr(sh, "memory_kind", None))

    @classmethod
    def _sig(cls, args):
        leaves, treedef = jax.tree.flatten(args)

        def leaf_sig(x):
            # shape/dtype/weak-type read straight off array leaves:
            # jax's shaped_abstractify also hashes the leaf's sharding
            # (jax 0.9), which raises on an unhashable one before
            # _sharding_key can key it. Python scalars have no shape,
            # and abstractify carries no sharding for them.
            aval = (x if hasattr(x, "shape") and hasattr(x, "dtype")
                    else jax.api_util.shaped_abstractify(x))
            # the input SHARDING is part of the executable contract
            # too: an AOT program compiled for replicated arrays must
            # not run against mesh-sharded ones (hosted + mesh runs
            # call the same op-replay program in both placements)
            sh = cls._sharding_key(getattr(x, "sharding", None))
            return (tuple(aval.shape), str(aval.dtype),
                    bool(getattr(aval, "weak_type", False)), sh)

        return treedef, tuple(leaf_sig(x) for x in leaves)

    def __call__(self, *args):
        return self.warm(*args)(*args)

    def warm(self, *args):
        """Materialize the executable for this argument signature —
        disk-load or compile — WITHOUT executing it: the fleet
        pre-warm entry point (serving.prewarm). Donated buffers are
        untouched (donation happens at execution, not compilation),
        so a warmed Simulation still runs."""
        key = self._sig(args)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._build(key, args)
            self._compiled[key] = fn
        return fn

    def scope_map(self) -> dict:
        """{module name: {HLO instruction name: op_name}} over every
        executable this wrapper materialised (obs.passcope.
        scope_map_of): the pass labels of a device trace taken without
        HLO protos. Read on request only, once per executable, so a run
        does no HLO-text work unless asked."""
        from ..obs import passcope
        if self._scopes is None:
            self._scopes = {}
        for key, fn in self._compiled.items():
            if key not in self._scopes:
                self._scopes[key] = passcope.scope_map_of(fn) or {}
        out = {}
        for m in self._scopes.values():
            out.update(m)
        return out

    def _build(self, key, args):
        from ..serving import aotcache
        fn = aotcache.load_or_compile(self._jit, self.cache_scope,
                                      key, args,
                                      undonated=self.undonated_jit)
        # memory observatory hook (obs.memscope): record the XLA
        # cost_analysis (flops, bytes accessed) and memory_analysis
        # (argument/output/temp/generated-code bytes) of every
        # executable this wrapper materializes — compile or disk-load.
        # Graceful on executables that refuse either analysis (loaded
        # disk entries, exotic backends): `available: False` with the
        # error recorded, never a failed build. The latest analysis is
        # also kept on the instance so callers holding the AotJit
        # (engine.sim's cost model) read it without knowing the scope.
        # The DECLARED donation rides along so memscope's donation
        # audit (shrink-campaign lever 4) can compare it against the
        # measured alias_bytes per executable without reaching back
        # into this wrapper.
        from ..obs import memscope
        self.analysis = memscope.observe_executable(
            self.cache_scope or getattr(self._fn, "__name__", "?"), fn,
            donated=self._jit_kwargs.get("donate_argnums", ()))
        return fn


def aot_jit(fn=None, **jit_kwargs):
    """Decorator/factory: like jax.jit but always executes through the
    AOT Compiled path. Static arguments are not supported — close over
    them and cache one AotJit per static configuration instead."""
    if fn is None:
        return lambda f: AotJit(f, **jit_kwargs)
    return AotJit(fn, **jit_kwargs)
