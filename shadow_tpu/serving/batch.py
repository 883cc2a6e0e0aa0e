"""Vmapped scenario batching: one compile, N cheap executions.

A parameter sweep's scenarios usually share one compiled shape —
identical :class:`engine.state.EngineConfig`, differing only in seed
and Shared scalars (stop time, RNG root, latency tables). Running
them one process each pays the XLA compile N times (or, with the
aotcache disk tier, one compile + N loads + N process startups).
This module runs them as ONE program: the scenarios' (Hosts,
HostParams, Shared) pytrees stack on a leading axis and the window
chunk program runs under ``jax.vmap``
(``engine.window.run_windows_batch_aot``), so an N-point sweep pays
one compile and N lanes of cheap execution per pass.

Determinism is untouched, and provably so: jax's while_loop batching
rule freezes a finished lane's carry, so each lane's window
trajectory — chunk boundaries, window counts, state bytes — is
exactly its individual run's. Every lane emits its OWN digest chain
(an :class:`obs.digest.DigestRecorder` per scenario, cadence records
on the same window boundaries a single run produces) and its own
perf-ledger entry, and ``tools/divergence.py`` exits 0 against the
same scenario run individually (tests/test_serving.py — the
acceptance proof).

Batch runs are deliberately plainer than ``Simulation.run``: no
hosted apps, no fault schedules, no pcap, no mesh, no
checkpoint/resume (a crashed batch re-runs from scratch — the fleet
treats a batch group like a ``cmd`` run). What they keep is the part
a sweep needs: digest chains, summaries, ledger entries, the fleet
liveness heartbeat.

CLI (dispatched from ``python -m shadow_tpu batch ...``)::

  python -m shadow_tpu batch a.xml b.xml c.xml [--digest-dir D]
  python -m shadow_tpu batch sweep.xml --seeds 1,2,3,4 [--stop-time 10s]

``fleet submit --batch`` enqueues the same thing as one slot with
per-member journal states (docs/serving.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


class BatchShapeError(ValueError):
    """The scenarios do not share one compiled shape (EngineConfig or
    array shapes differ) — run them individually, or align their
    configs."""


def _stack_trees(trees):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def check_same_shape(sims) -> None:
    """Every member must compile to the SAME program: identical
    EngineConfig (static shapes/knobs) and identical array shapes
    (topology size, app tables). Differing Shared *values* (seed,
    stop time, latency tables) are exactly what batching is for."""
    import jax

    cfg0 = sims[0].cfg
    for i, s in enumerate(sims[1:], 1):
        if s.cfg != cfg0:
            raise BatchShapeError(
                f"member {i} resolves a different EngineConfig than "
                f"member 0 — not one compiled shape:\n  0: {cfg0}\n  "
                f"{i}: {s.cfg}")
    shapes0 = jax.tree.map(lambda a: a.shape, (sims[0].hosts,
                                               sims[0].hp, sims[0].sh))
    for i, s in enumerate(sims[1:], 1):
        shapes = jax.tree.map(lambda a: a.shape, (s.hosts, s.hp, s.sh))
        if shapes != shapes0:
            raise BatchShapeError(
                f"member {i}'s state arrays differ in shape from "
                "member 0's (different topology/app tables?) — "
                "members must share one compiled shape")
    for i, s in enumerate(sims):
        if s.hosting is not None:
            raise BatchShapeError(
                f"member {i} hosts real processes; batching covers "
                "modeled scenarios only")
        if s.injector is not None:
            raise BatchShapeError(
                f"member {i} schedules faults; batching covers plain "
                "runs only (fault surgery needs per-run host state)")
        if s.cfg.tracecap:
            raise BatchShapeError(
                f"member {i} enables pcap tracing; batching covers "
                "plain runs only")


def run_batch(sims, names=None, digest_paths=None, digest_every=0,
              netscope_paths=None, verbose=False):
    """Run N same-shape Simulations as one vmapped program.

    `digest_paths` (optional, len N) gives each lane its own digest
    chain + manifest, recorded at `digest_every` (default
    obs.digest.DEFAULT_EVERY) — cadence and final records land on
    exactly the window boundaries the same scenario produces
    individually, so the chains are byte-comparable with
    tools/divergence.py. Returns a list of SimReport, one per lane
    (wall_seconds is the SHARED batch wall — ledger entries say so).

    When the shared config carries ``netscope``, each lane gets its
    own :class:`obs.netscope.NetScope` recorder sampled on its own
    chunk boundaries (frozen lanes stop sampling, like a single run
    stopping) and its SimReport carries a per-lane ``network`` report
    from its slice of the stacked [lanes, H, K, B] accumulator —
    byte-equal to the same scenario's individual run.
    `netscope_paths` (optional, len N) streams each lane's records to
    its own JSONL file.
    """
    import jax
    import jax.numpy as jnp

    from ..core.simtime import SIMTIME_MAX
    from ..engine import defs
    from ..engine.sim import SimReport
    from ..engine.state import hot_fields
    from ..engine.window import (pass_batches, pass_labels,
                                 run_windows_batch_aot)
    from ..obs import digest as DG
    from ..obs import netscope as NSC
    from ..obs import passcope as PCOPE

    B = len(sims)
    assert B >= 1
    check_same_shape(sims)
    cfg = sims[0].cfg
    for s in sims:
        assert not s._ran, "Simulation objects are single-use"
        s._ran = True
    names = list(names or [f"member{i}" for i in range(B)])

    nsrecs = None
    if cfg.netscope:
        if netscope_paths is not None:
            assert len(netscope_paths) == B
        nsrecs = [NSC.NetScope(netscope_paths[i]
                               if netscope_paths is not None else None)
                  for i in range(B)]
    elif netscope_paths is not None:
        raise BatchShapeError(
            "netscope_paths given but the members' EngineConfig has "
            "netscope off — the device histograms are a compiled "
            "shape, so enable it on every member")

    recorders = None
    if digest_paths is not None:
        assert len(digest_paths) == B
        every = digest_every or DG.DEFAULT_EVERY
        recorders = [DG.DigestRecorder(p, every=every)
                     for p in digest_paths]
        for s, dg in zip(sims, recorders):
            dg.write_manifest(DG.build_manifest(
                s.scenario, s.cfg, s.seed, s.sh, s.host_names, dg))

    # records must land on exact window boundaries (the engine.sim
    # contract): the shared chunk rule (hosted members are refused
    # above, so this is cfg.chunk_windows shrunk to the cadence)
    chunk = sims[0].effective_chunk(
        recorders[0].every if recorders is not None else 0)
    fn = run_windows_batch_aot(cfg, chunk, B)

    hosts = _stack_trees([s.hosts for s in sims])
    hp = _stack_trees([s.hp for s in sims])
    sh = _stack_trees([s.sh for s in sims])
    ws = jnp.stack([jnp.min(s.hosts.eq_next) for s in sims])
    we = jnp.where(ws == SIMTIME_MAX, ws, ws + sh.min_jump)

    H = cfg.num_hosts
    stops = np.array([int(s.sh.stop_time) for s in sims],
                     dtype=np.int64)
    total_windows = np.zeros(B, dtype=np.int64)
    done = np.zeros(B, dtype=bool)
    _pl = pass_labels(cfg, H)
    pass_acc = np.zeros((B, len(_pl)), dtype=np.int64)
    _hot = hot_fields(cfg)
    row_bytes = sum(
        int(np.prod(getattr(sims[0].hosts, f).shape[1:]))
        * getattr(sims[0].hosts, f).dtype.itemsize for f in _hot)

    # fleet liveness heartbeat (docs/fleet.md): the scheduler's
    # watchdog needs a wall-paced progress signal from batch children
    # exactly like single runs (engine.sim's per-chunk touch). The
    # per-loop write below paces it while chunks retire — but the
    # FIRST fn() call blocks through the whole vmapped XLA compile
    # (10-15+ min on chip, vs the 900 s default hang timeout), so a
    # background beater keeps the mtime moving during it; otherwise
    # the watchdog would SIGKILL a healthy compiling group into
    # retry -> the identical compile -> quarantine.
    hb_dir = os.environ.get("SHADOW_TPU_FLEET_RUN_DIR")
    hb_path = os.path.join(hb_dir, "heartbeat") if hb_dir else None
    hb_stop = None
    if hb_path is not None:
        import threading

        hb_ws = {"ws": 0}

        def _beat(stop):
            while not stop.wait(15.0):
                try:
                    with open(hb_path, "w") as f:
                        f.write(f"{hb_ws['ws']}\n")
                except OSError:
                    pass

        hb_stop = threading.Event()
        threading.Thread(target=_beat, args=(hb_stop,),
                         daemon=True).start()

    def lane(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    def record(i, kind):
        w = int(np.asarray(ws)[i])
        sim_ns = (min(w, int(stops[i])) if w < SIMTIME_MAX
                  else int(stops[i]))
        recorders[i].record(lane(hosts, i), H,
                            int(total_windows[i]), sim_ns, kind)

    wall0 = time.perf_counter()
    first_chunk_wall = None
    while True:
        if hb_path is not None:
            hb_ws["ws"] = int(np.asarray(ws).min())
            try:
                with open(hb_path, "w") as f:
                    f.write(f"{hb_ws['ws']}\n")
            except OSError:
                pass
        hosts, ws, we, n, pc = fn(hosts, hp, sh, ws, we)
        n_np = np.asarray(n)
        total_windows += n_np
        pass_acc += np.asarray(pc)
        if first_chunk_wall is None:
            first_chunk_wall = time.perf_counter() - wall0
        w_np = np.asarray(ws)
        if nsrecs is not None:
            # per-lane network samples from the stacked accumulator:
            # one record per chunk a lane was ACTIVE in (a frozen
            # lane's carry no longer moves — sampling it would add
            # records a single run never emits)
            ns_b = np.asarray(hosts.ns_hist)
            st_b = np.asarray(hosts.stats)
            sk_b = np.asarray(hosts.sk_used)
        for i in range(B):
            if done[i]:
                continue
            if nsrecs is not None:
                nsrecs[i].sample(
                    int(total_windows[i]),
                    min(int(w_np[i]), int(stops[i])),
                    ns_b[i], st_b[i], conns=int(sk_b[i].sum()))
            # the single-run record order, per lane: cadence when due
            # after the chunk, then the final record when the lane
            # completes — so chains byte-match individual runs
            if (recorders is not None
                    and recorders[i].due(int(total_windows[i]))):
                record(i, "cadence")
            if w_np[i] >= stops[i] or w_np[i] >= SIMTIME_MAX:
                if recorders is not None:
                    record(i, "final")
                done[i] = True
        if verbose:
            print(f"  batch: {int(done.sum())}/{B} done, windows="
                  f"{total_windows.tolist()}")
        if done.all():
            break
    if hb_stop is not None:
        hb_stop.set()
    wall = time.perf_counter() - wall0
    if recorders is not None:
        for dg in recorders:
            dg.close()

    warm = (wall - first_chunk_wall
            if first_chunk_wall is not None
            and wall > first_chunk_wall * 1.05 else None)
    from ..obs.memscope import hbm_peak_gbps
    device_kind = next(iter(hosts.stats.devices())).device_kind
    stats_b = np.asarray(hosts.stats)
    peaks_b = np.asarray(hosts.cap_peaks)
    ns_final = (np.asarray(hosts.ns_hist)
                if nsrecs is not None else None)
    reports = []
    for i in range(B):
        w = int(np.asarray(ws)[i])
        sim_ns = (min(int(stops[i]), w) if w < SIMTIME_MAX
                  else int(stops[i]))
        peaks = peaks_b[i].max(axis=0)
        capacity = {"rows": [
            ("event_queue", cfg.qcap, int(peaks[0])),
            ("socket_table", cfg.scap, int(peaks[1])),
            ("outbox", cfg.obcap, int(peaks[2])),
            ("nic_txq", cfg.txqcap, int(peaks[3])),
        ]}
        cost = {
            "row_bytes": row_bytes,
            "hot_columns": len(_hot),
            "pass_mix": {lbl: (size, int(nn)) for (lbl, size), nn in
                         zip(_pl, pass_acc[i])},
            "batch": pass_batches(cfg, H),
            "per_chip_hosts": H,
            "shards": 1,
            "warm_wall": warm,
            "hbm_peak_gbps": hbm_peak_gbps(device_kind),
        }
        network = {}
        if nsrecs is not None:
            # per-lane network report from this lane's slice of the
            # FINAL device histogram (not the last sample — the exact
            # construction engine.sim uses)
            network = NSC.report(ns_final[i])
            network["records"] = len(nsrecs[i].records)
            if nsrecs[i].path:
                network["path"] = nsrecs[i].path
            nsrecs[i].close()
        # per-lane lockstep occupancy (obs.passcope): each lane's own
        # pass mix against its own executed events — a skewed lane
        # shows its waste here, not averaged into the batch
        occ = PCOPE.occupancy(
            cost["pass_mix"],
            int(stats_b[i][:, defs.ST_EVENTS].sum()),
            cost["batch"])
        reports.append(SimReport(
            stats=stats_b[i], host_names=sims[i].host_names,
            sim_time_ns=sim_ns, wall_seconds=wall,
            windows=int(total_windows[i]), capacity=capacity,
            cost=cost, network=network, occupancy=occ))
    return reports


# --- CLI -------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="shadow_tpu batch",
        description="run N same-shape scenarios as one vmapped "
                    "program: one compile, N executions "
                    "(docs/serving.md)")
    p.add_argument("configs", nargs="+",
                   help="scenario XML path(s); with --seeds, exactly "
                        "one, replicated per seed")
    p.add_argument("--seeds", default=None, metavar="S1,S2,...",
                   help="replicate the single config across these "
                        "seeds (member ids <stem>-s<seed>)")
    p.add_argument("--stop-time", default=None, metavar="TIME",
                   help="override every member's stop time")
    p.add_argument("--runahead", default=None, metavar="TIME",
                   help="override every member's lookahead window")
    p.add_argument("--digest-dir", default=None, metavar="DIR",
                   help="per-member digest chains: DIR/<member>."
                        "digest.jsonl (+ manifests)")
    p.add_argument("--digest-paths", default=None, metavar="P1,P2,...",
                   help="explicit per-member digest chain paths "
                        "(comma-separated, member order; the fleet "
                        "worker points these at each member's run "
                        "directory)")
    p.add_argument("--digest-every", type=int, default=0,
                   metavar="WINDOWS")
    p.add_argument("--netscope", action="store_true",
                   help="network observatory (obs.netscope): device "
                        "histograms per lane, a per-lane network "
                        "report in each member's summary, and one "
                        "cross-lane ensemble JSON line (pooled + "
                        "per-lane percentiles)")
    p.add_argument("--netscope-dir", default=None, metavar="DIR",
                   help="per-member netscope time-series streams: "
                        "DIR/<member>.netscope.jsonl (implies "
                        "--netscope)")
    p.add_argument("--netscope-paths", default=None,
                   metavar="P1,P2,...",
                   help="explicit per-member netscope stream paths "
                        "(comma-separated, member order; the fleet "
                        "worker points these at each member's run "
                        "directory — implies --netscope)")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="persistent AOT executable cache "
                        "(docs/serving.md)")
    p.add_argument("--perf", nargs="?", const="", default=None,
                   metavar="LEDGER",
                   help="append one perf-ledger entry PER MEMBER "
                        "(events are the member's; the wall is the "
                        "shared batch wall, noted in the entry)")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--summary-json", action="store_true",
                   help="print one summary JSON line per member")
    args = p.parse_args(argv)

    if args.aot_cache:
        from . import aotcache as AC
        AC.install(args.aot_cache)

    from ..core.config import load_xml
    from ..core.simtime import parse_time

    if args.seeds:
        if len(args.configs) != 1:
            p.error("--seeds takes exactly one config XML")
        try:
            seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
        except ValueError:
            p.error(f"--seeds {args.seeds!r}: not integers")
        if not seeds:
            p.error("--seeds names no seeds")
        if len(set(seeds)) != len(seeds):
            p.error(f"--seeds {args.seeds!r} lists duplicates — "
                    "member ids (and their digest chains) are named "
                    "by seed, so duplicate lanes would interleave "
                    "into one chain file")
        stem = os.path.splitext(os.path.basename(args.configs[0]))[0]
        members = [(f"{stem}-s{s}", args.configs[0], s) for s in seeds]
    else:
        members = []
        for path in args.configs:
            members.append((os.path.splitext(
                os.path.basename(path))[0], path, None))
        if len({m[0] for m in members}) != len(members):
            p.error("duplicate member stems; give distinct config "
                    "basenames (per-member outputs are named by stem)")

    sims = []
    names = []
    for name, path, seed in members:
        try:
            scen = load_xml(path)
        except (OSError, ValueError) as e:
            p.error(f"{path}: {e}")
        if args.stop_time:
            scen.stop_time = parse_time(args.stop_time,
                                        default_unit="s")
        if seed is not None:
            scen.seed = seed
        from ..engine.sim import Simulation
        sim = Simulation(scen)
        if args.netscope or args.netscope_dir or args.netscope_paths:
            # the device histograms are part of the compiled shape, so
            # the knob must be set before Hosts allocation — rebuild
            # with the auto config flipped (topology is reused)
            import dataclasses
            sim = Simulation(scen, topology=sim.topo,
                             engine_cfg=dataclasses.replace(
                                 sim.cfg, netscope=True))
        if args.runahead:
            import jax.numpy as jnp
            ra = parse_time(args.runahead, default_unit="ms")
            sim.sh = sim.sh.replace(min_jump=jnp.int64(max(ra, 1)))
        sims.append(sim)
        names.append(name)

    digest_paths = None
    if args.digest_paths:
        digest_paths = [s for s in args.digest_paths.split(",") if s]
        if len(digest_paths) != len(sims):
            p.error(f"--digest-paths names {len(digest_paths)} paths "
                    f"for {len(sims)} members")
    elif args.digest_dir:
        os.makedirs(args.digest_dir, exist_ok=True)
        digest_paths = [os.path.join(args.digest_dir,
                                     f"{n}.digest.jsonl")
                        for n in names]

    netscope_paths = None
    if args.netscope_paths:
        netscope_paths = [s for s in args.netscope_paths.split(",")
                          if s]
        if len(netscope_paths) != len(sims):
            p.error(f"--netscope-paths names {len(netscope_paths)} "
                    f"paths for {len(sims)} members")
    elif args.netscope_dir:
        os.makedirs(args.netscope_dir, exist_ok=True)
        netscope_paths = [os.path.join(args.netscope_dir,
                                       f"{n}.netscope.jsonl")
                          for n in names]

    try:
        reports = run_batch(sims, names=names,
                            digest_paths=digest_paths,
                            digest_every=args.digest_every,
                            netscope_paths=netscope_paths,
                            verbose=args.verbose)
    except BatchShapeError as e:
        p.error(str(e))

    from . import aotcache as AC
    compile_cache = "miss" if AC.STATS["compiles"] else "hit"
    B = len(reports)
    for name, rep in zip(names, reports):
        s = rep.summary()
        line = {"member": name, "events": s["events"],
                "windows": s["windows"],
                "sim_seconds": s["sim_seconds"],
                "batch_wall_seconds": round(rep.wall_seconds, 3),
                "batch": B, "compile_cache": compile_cache}
        print(json.dumps(line), flush=True)
        if args.summary_json:
            print(json.dumps(s), flush=True)

    if args.netscope or args.netscope_dir or args.netscope_paths:
        # cross-lane percentile curves: pooled distribution + per-lane
        # tails per kind, from the lanes' final device histograms
        from ..obs import netscope as NSC
        ens = NSC.ensemble([
            [r.network["kinds"][n]["buckets"] for n in NSC.KIND_NAMES]
            for r in reports if r.network.get("kinds")])
        print(json.dumps({"netscope_ensemble": {
            "runs": ens.get("runs", 0),
            "kinds": {name: {f: k[f] for f in
                             ("count", "p50_us", "p90_us", "p99_us",
                              "lane_p50_us", "lane_p99_us")}
                      for name, k in ens.get("kinds", {}).items()},
        }}), flush=True)

    if args.perf is not None:
        import jax

        from ..obs import ledger as LG
        for name, rep, sim in zip(names, reports, sims):
            entry = LG.make_entry(
                scenario=name,
                fingerprint=LG.fingerprint_of(
                    sim.cfg, seed=sim.scenario.seed,
                    stop_ns=int(sim.scenario.stop_time),
                    batch=B),
                platform=jax.default_backend(),
                summary=rep.summary(), cost=rep.cost_model(),
                warm_wall=(round(rep.cost["warm_wall"], 3)
                           if rep.cost.get("warm_wall") else None),
                cold_wall=round(rep.wall_seconds
                                - (rep.cost.get("warm_wall") or 0), 3),
                note=(f"vmapped batch member ({B} lanes, one "
                      f"compile_cache={compile_cache} program; wall "
                      "is the SHARED batch wall, so the rate reads "
                      "as this member's share)"),
                cfg=sim.cfg)
            lpath = LG.append(entry, args.perf or None)
            if lpath:
                sys.stderr.write(
                    f"shadow_tpu: batch: perf ledger += {lpath} "
                    f"({name})\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
