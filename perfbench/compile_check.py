#!/usr/bin/env python3
"""Compile a cell's window-chunk program for a described TPU v5e, with
no chip attached: the rehearsal that finds what the chip's compiler
refuses, and how long the cold compile of a cell takes, before any
chip time is spent. Nothing runs, so it says nothing about results or
speed.

  JAX_PLATFORMS=cpu python3 perfbench/compile_check.py --workload NAME

Prints one JSON line: compile seconds and the program's
``memory_analysis()`` bytes, beside one chip's HBM.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench.lib import deploy, peaks, system
    from perfbench.lib.spec import Spec
    from shadow_tpu.engine.window import run_windows_aot

    jax.config.update("jax_enable_compilation_cache", False)
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    dep = deploy.build(spec.config(cell), spec.traffic(cell), args.seed)
    sim = system.Deployment(dep).build()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)
    t0 = jnp.min(sim.hosts.eq_next)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        (sim.hosts, sim.hp, sim.sh, t0, t0 + sim.sh.min_jump))
    fn = run_windows_aot(sim.cfg, sim.effective_chunk(64))
    t = time.perf_counter()
    compiled = fn._jit.lower(*shapes).compile()
    secs = time.perf_counter() - t
    m = compiled.memory_analysis()
    mem = {k: int(getattr(m, f"{k}_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias", "generated_code")}
    mem["total"] = (mem["argument"] + mem["output"] + mem["temp"]
                    - mem["alias"] + mem["generated_code"])
    hbm = peaks.peaks(dev.device_kind)["hbm_bytes"]
    print(json.dumps({"workload": args.workload, "hosts": len(sim.host_names),
                      "device_kind": dev.device_kind,
                      "compile_s": secs, "memory_bytes": mem,
                      "hbm_share": mem["total"] / hbm}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
