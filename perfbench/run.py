#!/usr/bin/env python3
"""The benchmark: one run of one cell of BENCHMARK.json on the chip.

  python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up builds the cell's deployment from its configuration and traffic
files and the seed, loads (or compiles) the window-chunk program by
running the deployment to a short stop, and then the measured window
repeats whole runs of the deployment through ``Simulation.run`` until
``--seconds`` have passed (lib/window.py). After the window the peak
device memory is read, the runs are freed, and the plain reference
(reference/) runs the same deployment on the host; lib/compare.py
decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the window and reports its per-layer metrics (metrics/*.py)
and the breakdown. The last line of standard output is one JSON
object; the numbers compared, each beside its limit, are the last
lines of standard error.

With no TPU, or fewer chips than the cell asks for, it exits 3 and
prints no result. The persistent compilation cache lives at
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the simulator's own executable cache (shadow_tpu/serving/aotcache.py,
# the CLI's --aot-cache): a fresh process loads the window program
# without tracing or lowering it
AOT_DIR = os.path.join(ROOT, ".aot_cache")
EXIT_USAGE, EXIT_NO_CHIP, EXIT_NO_PROGRAM = 2, 3, 4
TRACE_S = 1.5


def say(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def since_process_start() -> float:
    """Seconds since this process started (the kernel's start time, so
    interpreter start-up counts); the import-time clock as a fallback."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


class CompileCounter:
    """XLA compilations and persistent-cache loads, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        from shadow_tpu.serving import aotcache
        return (self.compiles, self.cache_hits,
                aotcache.STATS["compiles"])


def device_check(chips: int):
    """-> the devices, or None when this is not a run on the chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"no TPU: JAX found platform {devs[0].platform!r}; this "
            "benchmark measures the chip and never falls back")
        return None
    if len(devs) < chips:
        say(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        return None
    return devs


def stat_names():
    from shadow_tpu.engine import defs
    names = [n for n in dir(defs) if n.startswith("ST_")]
    return sorted(names, key=lambda n: getattr(defs, n))


def warm_stop(dep: dict) -> int:
    """The short stop that compiles or loads the chunk program: two
    windows past the first process start (the stop time is a dynamic
    scalar of the same program)."""
    first = min(p["start_ns"] for h in dep["hosts"] for p in h["processes"])
    return min(first + 2 * dep["runahead_ns"], dep["stop_ns"])


def reference_numbers(cfg: dict, dep: dict, runs: list):
    import importlib
    from perfbench.lib import compare, system
    t = time.perf_counter()
    ref = importlib.import_module(f"perfbench.reference.{cfg['reference']}")
    ref_stats, ref_names = ref.run(dep)
    ref_s = time.perf_counter() - t
    nums = compare.numbers(runs, stat_names(), ref_stats, ref_names,
                           system.overflow)
    return nums, ref_s


def measure(spec, cell, config, traffic, seed, seconds, trace, devs,
            hbm_bytes=None, aot_dir=None):
    """Set-up, the window and the comparison of one run of `cell` on
    `devs` -> (the result line's dict, the compared numbers' lines).
    Everything after the look for a chip."""
    import jax
    from perfbench.lib import compare, deploy, system, window
    from perfbench.lib import spec as specmod
    from shadow_tpu.serving import aotcache
    dep = deploy.build(config, traffic, seed)
    if aot_dir is not None:
        aotcache.install(aot_dir)
    cc = CompileCounter()
    t0 = time.perf_counter()
    deployment = system.Deployment(dep)
    t1 = time.perf_counter()
    warm = system.run_once(deployment.build(stop_ns=warm_stop(dep)))
    t2 = time.perf_counter()
    say(f"set-up: deployment {t1 - t0:.3f} s; warm-up to "
        f"{warm['sim_ns'] / 1e9:.3f} sim-s {t2 - t1:.3f} s "
        f"({warm['events']} events); the window program "
        f"{'compiled' if aotcache.STATS['compiles'] else 'loaded'} in "
        f"{aotcache.STATS['compile_wall_s'] + aotcache.STATS['load_wall_s']:.3f} s")
    del warm
    gc.collect()
    setup_s = since_process_start()
    c0 = cc.snapshot()
    say(f"set-up {setup_s:.3f} s: {c0[0]} XLA compiles in "
        f"{cc.compile_s:.3f} s, {c0[1]} persistent-cache loads")

    # --trace 1 profiles a short slice of the window: TRACE_S seconds
    # from the end of the digest recorder's first record, inside the
    # chunk that follows, mid-run and away from a record (the records
    # are timed on the host clock instead: record_frac). A whole chunk
    # traces to hundreds of MB of operation events, minutes to stop and
    # read. The stop runs on the timer's thread and the reference runs
    # meanwhile, after the window.
    tmp, timer = None, None
    if trace:
        tmp = tempfile.TemporaryDirectory(prefix="perfbench-")

        # host events from the runtime and the annotations only: the
        # Python tracer would record the stop itself into the slice. No
        # HLO protos: the window program's is large and the reduction
        # reads only operation names and times.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False

        stop_s = []

        def stop_slice():
            t = time.perf_counter()
            jax.profiler.stop_trace()
            stop_s.append(time.perf_counter() - t)

        def start_slice():
            nonlocal timer
            jax.profiler.start_trace(tmp.name, profiler_options=opts)
            timer = threading.Timer(TRACE_S, stop_slice)
            timer.start()
    chunks = window.ChunkClock(on_first=start_slice if trace else None)

    def run(sim):
        chunks.start_run()
        return system.run_once(sim, on_record=chunks)
    ann = jax.profiler.TraceAnnotation
    w = window.run_window(deployment.build, run, seconds, annotate=ann)
    c1 = cc.snapshot()
    runs = w["runs"]
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:cell["chips"]])
    del deployment
    gc.collect()

    first = runs[0]
    rx = window.realtime_x(runs, w["window_s"])
    say(f"window: {len(runs)} whole run(s) of {first['sim_ns'] / 1e9} "
        f"sim-s in {w['window_s']:.3f} s (build {w['build_s']:.3f} s); "
        f"{first['events']} events and {first['windows']} windows per "
        f"run; events/s {window.events_per_s(runs, w['window_s']):.1f}; "
        f"digest {first['digest']} over {first['digest_records']} records")
    say(f"window program: {chunks.windows} windows in chunks taking "
        f"{chunks.wall:.3f} s of host wall; digest records "
        f"{chunks.record_wall:.3f} s")
    say(f"compilations inside the window: {c1[0] - c0[0]} XLA compiles, "
        f"{c1[1] - c0[1]} persistent-cache loads, {c1[2] - c0[2]} window "
        "programs")
    if hbm_bytes:
        say(f"device memory peak {mem_peak} bytes "
            f"({100.0 * mem_peak / hbm_bytes:.2f}% of HBM)")

    nums, ref_s = reference_numbers(config, dep, runs)
    say(f"reference: {ref_s:.3f} s on the host")

    metrics, device = {}, {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace:
        from perfbench.lib.trace import Trace
        t = time.perf_counter()
        if timer is not None:
            timer.join()
        say(f"profiler: the stop took {sum(stop_s):.3f} s, of which "
            f"{time.perf_counter() - t:.3f} s after the reference")
        t = time.perf_counter()
        tr = Trace.load(tmp.name)
        tmp.cleanup()
        lo, hi = tr.first_start(), tr.last_end()
        busy = tr.busy_ns(lo, hi, tr.device_ids()[:cell["chips"]])
        ctx = {"runs": runs, "window_s": w["window_s"],
               "build_s": w["build_s"], "trace": tr, "trace_lo": lo,
               "trace_hi": hi, "busy_ns": busy,
               "chunk_s": chunks.wall, "chunk_windows": chunks.windows,
               "record_s": chunks.record_wall}
        for m in spec.per_layer(cell):
            v = specmod.reader(m).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = busy / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": tr.device_ops(lo, hi),
                     "idle_gaps": tr.idle_gaps(lo, hi)}
        say(f"trace read in {time.perf_counter() - t:.3f} s: busy "
            f"{busy / 1e9:.3f} s of a {(hi - lo) / 1e9:.3f} s slice")
    else:
        values = {"realtime_x": rx, "hbm_peak_gb": mem_peak / 1e9,
                  "setup_s": setup_s}
        for m in spec.end_to_end(cell):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    failed = sum(1 for x in runs
                 if system.overflow(x)
                 or x["digest"] != first["digest"]
                 or (x["stats"] != first["stats"]).any())
    out = {"correct": compare.judge(nums), "attempted": len(runs),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": nums[k], "limit": compare.LIMITS[k]}
                       for k in compare.LIMITS}
    return out, compare.lines(nums)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, ROOT)
    from perfbench.lib import spec as specmod
    try:
        spec = specmod.Spec(ROOT)
        cell = spec.cell(args.workload)
    except specmod.SpecError as e:
        say(str(e))
        return EXIT_USAGE
    config, traffic = spec.config(cell), spec.traffic(cell)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = device_check(cell["chips"])
    if devs is None:
        return EXIT_NO_CHIP
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # every program, however quick to compile, so that a run after the
    # checkout's first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from perfbench.lib import peaks
    hbm = peaks.peaks(devs[0].device_kind)["hbm_bytes"]
    try:
        import shadow_tpu.engine.sim  # noqa: F401
    except ImportError as e:
        say(f"the system under test is not importable: {e}")
        return EXIT_NO_PROGRAM
    out, compared = measure(spec, cell, config, traffic, args.seed,
                            args.seconds, args.trace, devs, hbm_bytes=hbm,
                            aot_dir=AOT_DIR)
    for line in compared:
        say(f"compare {line}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
